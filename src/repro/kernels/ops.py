"""Public jit'd wrappers for the Pallas kernels.

Handles padding to tile boundaries, the pytree <-> flat-stream view, the
custom_vjp wiring for the fused VT loss, and the `interpret` default: kernels
compile for the TPU and run in interpret mode on the CPU, where the tests
check them (see `_interpret_default`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.virtual_teacher import teacher_entropy
from repro.kernels import decdiff_update as _dd
from repro.kernels import neighbor_avg as _na
from repro.kernels import vt_kl_loss as _vt
from repro.utils.pytree import tree_flatten_to_vector


def _interpret_default() -> bool:
    """Compiled on the TPU, interpreted on the CPU (tests); any other
    backend has no Mosaic lowering and is refused, never interpreted."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels compile for the TPU and run interpreted on the "
            f"CPU; backend {backend!r} is neither")
    return backend == "cpu"


def _pad_to(x, multiple, value=0.0):
    n = x.shape[0]
    pad = (-n) % multiple
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1), constant_values=value)
    return x


# ------------------------------------------------------------- decdiff


@functools.partial(jax.jit, static_argnames=("s", "interpret"))
def decdiff_update(w_flat, wbar_flat, s: float = 1.0, interpret=None):
    """Eq. 5 on flat vectors via the two-pass Pallas stream."""
    interpret = _interpret_default() if interpret is None else interpret
    n = w_flat.shape[0]
    tile = _dd.BLOCK_ROWS * _dd.LANES
    w = _pad_to(w_flat.astype(jnp.float32), tile).reshape(-1, _dd.LANES)
    wb = _pad_to(wbar_flat.astype(jnp.float32), tile).reshape(-1, _dd.LANES)
    # pad region contributes (wb-w)=0 to the norm because both pads are 0.
    partials = _dd.sumsq_diff_blocks(w, wb, interpret=interpret)
    d = jnp.sqrt(jnp.sum(partials))
    scale = (1.0 / (d + s)).reshape(1, 1)
    out = _dd.scaled_step_blocks(w, wb, scale, interpret=interpret)
    return out.reshape(-1)[:n].astype(w_flat.dtype)


def decdiff_update_tree(params, avg_params, s: float = 1.0, interpret=None):
    """Pytree-level DecDiff step backed by the flat-stream kernel."""
    w, unflatten = tree_flatten_to_vector(params)
    wbar, _ = tree_flatten_to_vector(avg_params)
    return unflatten(decdiff_update(w, wbar, s=s, interpret=interpret))


# ------------------------------------------------------------- vt loss


def _vt_stats(z, labels, interpret):
    b, v = z.shape
    zp = jnp.pad(z, ((0, (-b) % _vt.ROWS), (0, (-v) % _vt.VCOLS)))
    lp = jnp.pad(labels.astype(jnp.int32), (0, (-b) % _vt.ROWS),
                 constant_values=-1)
    mx = _vt.row_max(zp, v, interpret=interpret)
    stats = _vt.row_stats(zp, lp, mx, v, interpret=interpret)
    return zp, lp, mx, stats


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def vt_kl_loss_fused(logits, labels, beta: float = 0.95, interpret=None):
    """Mean KL(p_t || softmax(logits)) — Eq. 8 — fused over the vocab axis.

    logits [B, V] (fp32/bf16), labels [B] int32.  custom_vjp: backward is the
    fused (softmax - p_t) kernel, so autodiff never materializes the teacher.
    """
    loss, _ = _vt_fwd(logits, labels, beta, interpret)
    return loss


def _vt_loss_from_stats(z, labels, mx, stats, beta):
    b, v = z.shape
    sumexp, zsum, zc = stats[:b, 0], stats[:b, 1], stats[:b, 2]
    mxb = mx[:b]
    lse = jnp.log(sumexp) + mxb
    a = (1.0 - beta) / (v - 1)
    cross = beta * zc + a * (zsum - zc) - lse
    return jnp.mean(-teacher_entropy(beta, v) - cross)


def _vt_fwd(logits, labels, beta, interpret):
    interpret = _interpret_default() if interpret is None else interpret
    z = logits.astype(jnp.float32)
    zp, lp, mx, stats = _vt_stats(z, labels, interpret)
    loss = _vt_loss_from_stats(z, labels, mx, stats, beta)
    return loss, (logits, zp, lp, mx, stats)


def _vt_bwd(beta, interpret, res, g):
    interpret_ = _interpret_default() if interpret is None else interpret
    logits, zp, lp, mx, statsp = res
    b, v = logits.shape
    dtype = logits.dtype
    sumexp = jnp.pad(statsp[:, 0], (0, zp.shape[0] - statsp.shape[0]),
                     constant_values=1.0)
    gscale = (g / b).reshape(1, 1).astype(jnp.float32)
    grad = _vt.vt_backward(zp, lp, mx, sumexp, gscale, beta=beta, vocab=v,
                           interpret=interpret_)
    return grad[:b, :v].astype(dtype), None


vt_kl_loss_fused.defvjp(_vt_fwd, _vt_bwd)


# ------------------------------------------------------------- decode attn


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention_fused(q, k_cache, v_cache, slot_pos, pos, interpret=None):
    """Fused one-token GQA attention over a ring cache (serve hot spot).

    q [B,H,hd]; k/v [B,W,K,hd]; slot_pos [W] absolute positions (-1 empty);
    pos scalar current position.  Matches layers.decode_attention's
    score/softmax/combine (output fp32)."""
    from repro.kernels import decode_attention as _da

    interpret = _interpret_default() if interpret is None else interpret
    b, h, hd = q.shape
    w = k_cache.shape[1]
    pad_b = (-b) % _da.B_BLK
    pad_w = (-w) % _da.W_BLK
    qp = jnp.pad(q.astype(jnp.float32), ((0, pad_b), (0, 0), (0, 0)))
    kp = jnp.pad(k_cache, ((0, pad_b), (0, pad_w), (0, 0), (0, 0)))
    vp = jnp.pad(v_cache, ((0, pad_b), (0, pad_w), (0, 0), (0, 0)))
    spp = jnp.pad(slot_pos.astype(jnp.int32), (0, pad_w), constant_values=-1)
    pos2 = jnp.reshape(pos.astype(jnp.int32), (1, 1))
    out = _da.decode_attention_blocks(qp, kp, vp, spp, pos2,
                                      interpret=interpret)
    return out[:b]


# ------------------------------------------------------------- neighbor avg


@functools.partial(jax.jit, static_argnames=("interpret",))
def neighbor_avg(stacked, weights, interpret=None):
    """Eq. 6: normalized ω_ij p_ij-weighted average of stacked [N, D] rows."""
    interpret = _interpret_default() if interpret is None else interpret
    n, d = stacked.shape
    w = weights.astype(jnp.float32)
    w = w / jnp.sum(w)
    pad = (-d) % _na.COLS
    sp = jnp.pad(stacked.astype(jnp.float32), ((0, 0), (0, pad)))
    out = _na.neighbor_avg_blocks(sp, w, interpret=interpret)
    return out[:d]


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequant_neighbor_avg(q, scales, weights, interpret=None):
    """Eq. 6 over int8 comm payloads: dequantize-and-accumulate in one pass.

    q [N, D] int8 rows (the neighbours' wire payloads), scales [N] fp32
    per-row quantization scales, weights [N] ω_ij p_ij (normalized here).
    Equals neighbor_avg(q * scales[:, None], weights) without ever writing
    the dequantized models back to HBM.
    """
    from repro.kernels import dequant_avg as _dqa

    interpret = _interpret_default() if interpret is None else interpret
    n, d = q.shape
    w = weights.astype(jnp.float32)
    w = w / jnp.sum(w)
    ws = w * scales.astype(jnp.float32)
    pad = (-d) % _dqa.COLS
    qp = jnp.pad(q.astype(jnp.int8), ((0, 0), (0, pad)))
    out = _dqa.dequant_avg_blocks(qp, ws, interpret=interpret)
    return out[:d]


@functools.partial(jax.jit, static_argnames=("interpret",))
def segment_neighbor_avg_rows(table, idx, w, interpret=None):
    """Ragged neighbor reduce over a table: per-receiver (Σ_k w·table[idx],
    Σ_k w) in one kernel pass that gathers the rows itself.

    table [M, D] f32 rows (decoded models, a per-edge bank), idx [B, K]
    int32 row ids (any valid row wherever w is 0), w [B, K] f32
    unnormalized gossip weights (0 at padding/undelivered slots) ->
    (sums [B, D], tot [B]).

    The totals come out of the same per-row contraction as the sums (a
    separate `jnp.sum(w)` would not be bitwise K-width-invariant), and
    zero-weight slots are never fetched.  Each receiver row is contracted
    independently inside the kernel (see `repro.kernels.segment_avg`): the
    result is bitwise invariant to B, row blocking, and K zero-padding —
    the dense engine at small N is therefore an exact oracle for this
    path.  K is zero-padded to a multiple of 8 so the kernel's dot always
    has a sublane-aligned contraction width, whatever the graph's degrees.
    """
    from repro.kernels import segment_avg as _sa

    interpret = _interpret_default() if interpret is None else interpret
    d = table.shape[1]
    b, k = idx.shape
    pad = ((0, (-b) % _sa.ROWS), (0, (-k) % _sa.K_ALIGN))
    wp = jnp.pad(w.astype(jnp.float32), pad)
    ip = jnp.where(wp != 0, jnp.pad(idx.astype(jnp.int32), pad), -1)
    cols = _sa.gather_cols(d, wp.shape[1], interpret)
    sums, tot = _sa.segment_avg_gather(
        ip, wp, table.astype(jnp.float32)[:, None, :], cols=cols,
        interpret=interpret)
    return sums[:b], tot[:b, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def segment_neighbor_avg(vals, w, interpret=None):
    """The panel form: vals [B, K, D] f32 slot-padded neighbour rows
    (garbage allowed wherever w is 0), w [B, K] -> (sums [B, D], tot [B]).

    The per-edge transport's reconstructions and the delta forms
    (x_k - local) exist only as a panel; slot k of receiver b is row
    b·K + k of the flattened panel, through the one table-form kernel."""
    b, k, d = vals.shape
    idx = jnp.arange(b * k, dtype=jnp.int32).reshape(b, k)
    return segment_neighbor_avg_rows(vals.reshape(b * k, d), idx, w,
                                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequant_segment_neighbor_avg(q, scales, w, interpret=None):
    """Ragged dequantize-and-reduce over int8 payload blocks.

    q [B, K, D] int8 slot-padded wire payloads, scales [B, K] f32 per-slot
    quantization scales, w [B, K] f32 gossip weights -> sums [B, D] f32,
    Σ_k (w_k·s_k)·q_k per receiver.  Sums only: normalization totals must
    come from `segment_neighbor_avg`'s ones-column path so their bits match
    the f32 route (the fused w·s product here associates differently from
    w·(s·q), so this is the fast path, not the oracle-pinned one).
    """
    from repro.kernels import segment_avg as _sa

    interpret = _interpret_default() if interpret is None else interpret
    b, k, d = q.shape
    pk = (-k) % _sa.K_ALIGN
    qp = jnp.pad(q.astype(jnp.int8),
                 ((0, (-b) % _sa.ROWS), (0, pk), (0, (-d) % _sa.COLS)))
    ws = w.astype(jnp.float32) * scales.astype(jnp.float32)
    wsp = jnp.pad(ws, ((0, (-b) % _sa.ROWS), (0, pk)))
    bp, k, dp = qp.shape
    out = jax.lax.map(
        lambda args: _sa.dequant_segment_avg_chunk(args[0], args[1],
                                                   interpret=interpret),
        (wsp.reshape(bp // _sa.ROWS, _sa.ROWS, k),
         qp.reshape(bp // _sa.ROWS, _sa.ROWS, k, dp)))
    return out.reshape(bp, dp)[:b, :d]


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequant_neighbor_avg_rows(q, scales, wn, interpret=None):
    """Eq. 6 for a BLOCK of receivers over int8 comm payloads, fused.

    q [N, D] int8 rows (the all_gathered wire payloads), scales [N] fp32
    per-sender quantization scales, wn [R, N] per-receiver gossip weights
    — already row-normalized by the caller (the shard_map round masks and
    normalizes before slicing its pod block; an all-zero row yields an
    all-zero average, the "heard from nobody" case).  Equals
    wn @ (q * scales[:, None]) without materializing the dequantized
    models: each int8 tile is loaded once and reused for all R receivers.
    """
    from repro.kernels import dequant_avg as _dqa

    interpret = _interpret_default() if interpret is None else interpret
    d = q.shape[1]
    ws = wn.astype(jnp.float32) * scales.astype(jnp.float32)[None, :]
    pad = (-d) % _dqa.COLS
    qp = jnp.pad(q.astype(jnp.int8), ((0, 0), (0, pad)))
    out = _dqa.dequant_avg_rows_blocks(qp, ws, interpret=interpret)
    return out[:, :d]
