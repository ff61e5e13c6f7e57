"""Jit-able steps: train/prefill/serve and the pod-level DFL round.

The DFL round is the paper's Algorithm 1 executed over a stacked node axis:
every node takes a local SGD step on its own shard of the synthetic stream,
then DecDiff gossip (core/decdiff.py, Eq. 5-6) moves each node toward its
neighbourhood average with the distance-attenuated step.  Two executions:

  * `build_dfl_round`          — vmap over the node axis; on the production
    mesh the node dim shards over "pod" via in_shardings (launch/dryrun.py).
  * `build_dfl_round_shardmap` — explicit shard_map over the "pod" axis:
    neighbour models move with an all_gather over the pod ring and each pod
    applies Eq. 5-6 to its own nodes (see its docstring for the manual-axes
    rationale).

Both support per-neighbour delivery masks: the paper imposes no round
synchronization, so a node may hear from any subset of its neighbours; a
masked neighbour contributes nothing and a fully-masked node keeps its local
model (see `decdiff_aggregate_stacked`).

Both also accept a repro.comm codec: the exchange then carries the encoded
payload (int8 / top-k wire format — in the shard_map round the all_gather
itself moves the payload, which is the real inter-pod traffic win) and every
receiver dequantizes before DecDiff, leaving Eq. 5-6 semantics unchanged.
For int8 the shard_map round fuses the dequantization into the Eq. 6
reduction with the `dequant_neighbor_avg_rows` Pallas kernel (the gathered
payload is reduced directly; the fp32 neighbour models are never
materialized), with the vmap round as the equivalence oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.comm.codecs import Int8Codec
from repro.comm.transport import codec_roundtrip_stacked
from repro.core.decdiff import DEFAULT_S
from repro.dist.sharding import NODE_AXIS
from repro.utils.pytree import tree_flatten_stacked


def _normalized(adj, mask):
    adj = jnp.asarray(adj, jnp.float32)
    if mask is not None:
        adj = adj * jnp.asarray(mask, jnp.float32)
    row = jnp.sum(adj, axis=1)
    return adj / jnp.where(row > 0, row, 1.0)[:, None], row


def _decdiff_step_from_avg(local, avg, row, s):
    """Eq. 5 for a block of nodes, given the Eq. 6 average.

    `local` has leaves [R, ...] (the nodes being updated), `avg` the
    like-structured precomputed neighbourhood averages (fp32), `row` [R]
    the pre-normalization weight-row sums (0 -> the node heard from nobody
    and keeps its local model).  The SINGLE implementation of the
    gating/dtype rules — every execution of the round (vmap, shard_map,
    fused-payload shard_map) funnels through it so the rules cannot
    diverge.
    """
    diff = jax.tree.map(lambda a, x: a - x.astype(jnp.float32), avg, local)
    sq = jax.tree.reduce(
        jnp.add,
        jax.tree.map(
            lambda d: jnp.sum(jnp.square(d), axis=tuple(range(1, d.ndim))),
            diff),
    )
    scale = jnp.where(row > 0, 1.0 / (jnp.sqrt(sq) + s), 0.0)

    def step_leaf(x, d):
        sc = scale.reshape(scale.shape + (1,) * (d.ndim - 1))
        return (x.astype(jnp.float32) + sc * d).astype(x.dtype)

    return jax.tree.map(step_leaf, local, diff)


def _decdiff_apply(local, full, wn, row, s):
    """Eq. 6 then Eq. 5 for a block of nodes: `full` has leaves [N, ...]
    (every candidate neighbour, already cast for the exchange), `wn` [R, N]
    row-normalized weights."""
    avg = jax.tree.map(
        lambda x: jnp.einsum("rj,j...->r...", wn, x.astype(jnp.float32)), full)
    return _decdiff_step_from_avg(local, avg, row, s)


def decdiff_gossip(stacked, adj, s=DEFAULT_S, *, mask=None, gossip_dtype=None,
                   codec=None):
    """DecDiff aggregation for all nodes at once.

    Args:
      stacked: pytree with leaves [N, ...] — one model per node.
      adj: [N, N] non-negative gossip weights (omega_ij * p_ij); rows are
        normalized internally, the diagonal should be zero (Eq. 6 excludes
        the local model).
      s: the paper's denominator offset (Eq. 5).
      mask: optional [N, N] {0, 1} delivery mask; mask[i, j] = 0 means node i
        did not receive node j's model this round.
      gossip_dtype: optional dtype the exchanged models are cast to before
        averaging (e.g. bf16 gossip halves inter-pod traffic); the norm and
        the update stay fp32.
      codec: optional repro.comm codec modelling the wire: every exchanged
        model is encode->decode roundtripped (deterministic, reference-free)
        before averaging — dequantize-then-DecDiff, so Eq. 5-6 semantics are
        unchanged and the local (un-roundtripped) model stays exact.
        Takes precedence over `gossip_dtype`.

    Returns the updated stacked models; matches per-node
    `decdiff_aggregate` to fp32 round-off.
    """
    wn, row = _normalized(adj, mask)
    if codec is not None:
        full = codec_roundtrip_stacked(codec, stacked)
    elif gossip_dtype is not None:
        full = jax.tree.map(lambda x: x.astype(gossip_dtype), stacked)
    else:
        full = stacked
    return _decdiff_apply(stacked, full, wn, row, s)


def _make_node_step(lm, opt, loss_kind, beta):
    def loss_fn(params, batch):
        total, metrics = lm.loss(params, batch, loss_kind=loss_kind, beta=beta)
        return total, metrics

    def node_step(params, opt_state, step, batch):
        (total, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        new_params, new_state = opt.update(grads, opt_state, params, step)
        return new_params, new_state, total

    return node_step


def build_train_step(lm, opt, *, loss_kind: str = "vt", beta: float = 0.98):
    """(params, opt_state, step, batch) -> (params, opt_state, loss) for a
    single model replica (data-parallel / centralized reference)."""
    return _make_node_step(lm, opt, loss_kind, beta)


def build_prefill_step(lm):
    """(params, batch) -> logits — the forward pass, teacher-forced."""

    def prefill_step(params, batch):
        logits, _ = lm.forward(params, batch)
        return logits

    return prefill_step


def build_serve_step(lm):
    """(params, cache, tokens [B, 1]) -> (logits, cache) — one decode step
    against the ring-buffer KV / recurrent cache."""

    def serve_step(params, cache, tokens):
        return lm.decode_step(params, cache, tokens)

    return serve_step


def build_dfl_round(lm, opt, adj, *, loss_kind: str = "vt", beta: float = 0.98,
                    s=DEFAULT_S, gossip_dtype=None, mask=None, codec=None):
    """One DFL communication round over stacked per-node state.

    (params [N,...], opt_state [N,...], step, batch [N,B,S], mask=None) ->
    (params, opt_state, mean loss).  Local SGD steps run vmapped over the
    node axis, then DecDiff gossip with the fixed `adj` couples the nodes.

    Delivery masks: the builder kwarg `mask` bakes a fixed [N, N] mask in;
    the round function additionally accepts a runtime `mask` (overriding the
    baked one), so per-round stochastic delivery — the paper's
    no-synchronization model — needs no retrace.

    `codec` (repro.comm) compresses the gossip exchange: neighbours see the
    encode->decode roundtrip of each model (the local model and the norm
    stay exact).  Use a deterministic codec so this round stays equal to the
    shard_map formulation.
    """
    adj = jnp.asarray(adj, jnp.float32)
    node_step = _make_node_step(lm, opt, loss_kind, beta)
    built_mask = mask

    def round_fn(params, opt_state, step, batch, mask=None):
        new_params, new_state, losses = jax.vmap(
            node_step, in_axes=(0, 0, None, 0))(params, opt_state, step, batch)
        m = mask if mask is not None else built_mask
        new_params = decdiff_gossip(new_params, adj, s=s, mask=m,
                                    gossip_dtype=gossip_dtype, codec=codec)
        return new_params, new_state, jnp.mean(losses)

    return round_fn


def build_dfl_round_shardmap(lm, opt, adj, mesh, *, loss_kind: str = "vt",
                             beta: float = 0.98, s=DEFAULT_S,
                             gossip_dtype=None, mask=None, codec=None,
                             fuse_dequant: bool = True):
    """`build_dfl_round` as an explicit shard_map over the "pod" axis.

    Each pod holds `N / n_pods` nodes; the gossip exchange is an all_gather
    of the post-step models over the pod ring (cast to `gossip_dtype` first
    when set).  All mesh axes are manual, so each pod holds its nodes' full
    replicas and Eq. 5's global squared norm is complete blockwise, no
    cross-axis reduction needed.  Delivery masks follow
    `build_dfl_round`: a baked builder `mask` plus an optional runtime
    `mask` argument on the round function.  Falls back to the vmap
    formulation when the mesh has no pod axis.

    With a `codec` (repro.comm) the all_gather moves the *encoded payload*
    (e.g. int8 values + one fp32 scale per node) instead of fp32 models —
    the actual inter-pod wire reduction.  For the int8 codec the post-gather
    path is KERNELIZED by default (`fuse_dequant=True`): instead of
    decode-then-average (which materializes N dequantized fp32 models — 4x
    the payload footprint plus an extra HBM round trip), the Pallas kernel
    `repro.kernels.dequant_neighbor_avg_rows` folds the per-sender scales
    into the Eq. 6 weights and reduces the int8 payload directly; Eq. 5 then
    runs on the flat per-pod block.  `fuse_dequant=False` keeps the
    decode-then-average formulation (the equivalence oracle, together with
    `build_dfl_round(codec=...)`).  The codec must be deterministic
    (stochastic=False for int8) so this round matches the vmap round.
    """
    if NODE_AXIS not in mesh.shape:
        return build_dfl_round(lm, opt, adj, loss_kind=loss_kind, beta=beta,
                               s=s, gossip_dtype=gossip_dtype, mask=mask,
                               codec=codec)

    adj = jnp.asarray(adj, jnp.float32)
    n_nodes = int(adj.shape[0])
    n_pods = int(mesh.shape[NODE_AXIS])
    if n_nodes % n_pods:
        raise ValueError(f"{n_nodes} DFL nodes do not tile the "
                         f"{n_pods}-pod axis")
    per_pod = n_nodes // n_pods
    node_step = _make_node_step(lm, opt, loss_kind, beta)
    built_mask = (jnp.asarray(mask, jnp.float32) if mask is not None
                  else jnp.ones_like(adj))
    fused_int8 = (fuse_dequant and isinstance(codec, Int8Codec))

    def gather_full(new_params):
        """The gossip exchange: what actually crosses the pod ring.

        codec set   -> all_gather the encoded payload (int8/top-k wire
                       format), dequantize after the gather;
        dtype set   -> all_gather the cast models (bf16 gossip);
        neither     -> all_gather the fp32 models.
        """
        if codec is not None:
            w, unflatten = tree_flatten_stacked(new_params)  # [per_pod, D]
            d = int(w.shape[1])
            payload, _ = jax.vmap(lambda xi: codec.encode(xi))(w)
            gathered = jax.tree.map(
                lambda x: jax.lax.all_gather(x, NODE_AXIS, axis=0, tiled=True),
                payload)
            dec = jax.vmap(lambda p: codec.decode(p, out_size=d))(gathered)
            return unflatten(dec)  # [N, ...] reconstructed models
        cast = ((lambda x: x.astype(gossip_dtype))
                if gossip_dtype is not None else (lambda x: x))
        return jax.tree.map(
            lambda x: jax.lax.all_gather(cast(x), NODE_AXIS, axis=0,
                                         tiled=True),
            new_params)

    def fused_block(new_params, wn_blk, row_blk):
        """Eq. 6 on the gathered int8 payload with dequantization fused
        into the reduction (dequant_neighbor_avg_rows) — the reconstructed
        fp32 neighbour models never exist in HBM — then the shared Eq. 5
        step on the flat [per_pod, D] view (`unflatten` restores leaf
        dtypes)."""
        from repro.kernels import dequant_neighbor_avg_rows

        w_local, unflatten = tree_flatten_stacked(new_params)  # [R, D] fp32
        payload, _ = jax.vmap(lambda xi: codec.encode(xi))(w_local)
        gathered = jax.tree.map(
            lambda x: jax.lax.all_gather(x, NODE_AXIS, axis=0, tiled=True),
            payload)  # q [N, D] int8, scale [N] fp32
        avg = dequant_neighbor_avg_rows(gathered["q"], gathered["scale"],
                                        wn_blk)  # [R, D]
        out = _decdiff_step_from_avg({"w": w_local}, {"w": avg}, row_blk, s)
        return unflatten(out["w"])

    def block(params, opt_state, step, batch, mask):
        new_params, new_state, losses = jax.vmap(
            node_step, in_axes=(0, 0, None, 0))(params, opt_state, step, batch)
        wn, row = _normalized(adj, mask)
        i0 = jax.lax.axis_index(NODE_AXIS) * per_pod
        wn_blk = jax.lax.dynamic_slice_in_dim(wn, i0, per_pod, axis=0)
        row_blk = jax.lax.dynamic_slice_in_dim(row, i0, per_pod, axis=0)
        if fused_int8:
            out = fused_block(new_params, wn_blk, row_blk)
        else:
            full = gather_full(new_params)
            out = _decdiff_apply(new_params, full, wn_blk, row_blk, s)
        loss = jax.lax.pmean(jnp.mean(losses), NODE_AXIS)
        return out, new_state, loss

    sharded = jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(NODE_AXIS), P(NODE_AXIS), P(), P(NODE_AXIS), P()),
        out_specs=(P(NODE_AXIS), P(NODE_AXIS), P()),
        check_vma=False)

    def round_fn(params, opt_state, step, batch, mask=None):
        m = mask if mask is not None else built_mask
        return sharded(params, opt_state, step, batch, m)

    return round_fn
