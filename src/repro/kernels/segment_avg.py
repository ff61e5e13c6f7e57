"""Ragged segment neighbor-average kernels (the engine's neighbour reduce).

`neighbor_avg` / `dequant_neighbor_avg_rows` assume one dense `[N, D]` /
`[R, N]` weight panel — O(N^2) state.  The engine instead names each
receiver's K neighbours by row ids into an `[M, D]` table (the decoded
models, a per-edge bank, or a flattened slot panel) and reduces them here.

Gather form (`segment_avg_gather`): the neighbour ids ride as scalar
prefetch, and each slot k of a receiver has its own input BlockSpec whose
index map reads `idx[b, k]`, so the pipeline DMAs exactly that table row's
column tile from HBM into VMEM.  The `[B, K, D]` neighbour panel never
exists in HBM.  The table is viewed as `[M, 1, D]`, which the compiler
lays out row-contiguous, so every slot DMA moves one contiguous run of
bytes.  A slot whose weight is 0 (K padding, an undelivered edge) is
pointed at row 0, column tile 0, so its block index never changes and the
pipeline fetches it once per call, not once per tile.

Bitwise contract: each receiver row is contracted by its OWN `(1, K) ·
(K, cols)` dot inside the kernel body.  A batched contraction's bits depend
on the batch geometry (probed: `einsum("bk,bkd->bd")` at B=100 differs from
the same rows at B=1), so per-row contraction is what makes the result
invariant to how receivers are blocked into pods or degree buckets — the
property the dense-oracle equivalence rests on.  Column tiling cannot
change bits either: each output element accumulates over the K axis of its
own column only, so its addition order is the same in any tile width, and
a partial last tile only leaves columns past D undefined, which no output
keeps.  The same argument gives the totals: the weights contracted with a
`(K, 128)` block of ones, by the same per-row dot, equal a ones column
riding in the table bit for bit, without a padded copy of the table.
Zero-weight slots are bit-neutral for any finite slot values: a `0.0 * x`
term adds ±0.0, which never perturbs an IEEE accumulator.

`dequant_segment_avg_chunk` is the int8 wire's panel-form reduce; callers
drive fixed `[ROWS, K, D]` chunks of it through `lax.map`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8  # receiver rows per output block / per int8 chunk
COLS = 256  # feature columns per grid tile of the int8 chunk kernel
K_ALIGN = 8  # callers zero-pad the slot axis K to a multiple of this
LANES = 128  # lane width: column tiles are whole multiples of it
GATHER_ELEMS = 1 << 20  # K × cols f32 of one receiver's slot tiles (4 MiB)


def _row_dot(w, v):
    """One receiver row's own contraction: (1, K) · (K, cols) -> (1, cols).

    Rank-2 on both sides, because Mosaic lowers no rank-1 dot; HIGHEST so
    the MXU keeps fp32 accuracy."""
    return jnp.dot(w, v, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def gather_cols(d: int, k: int, interpret: bool) -> int:
    """Column-tile width of the gather kernel for a `d`-wide table.

    On hardware K slot tiles, double-buffered, must fit VMEM, so the tile
    narrows as K grows: K × cols stays within GATHER_ELEMS, split evenly
    over the fewest whole-lane tiles (a table no wider than that is one
    tile).  In interpret mode every grid point unrolls into the caller's
    trace, so one full-width tile keeps the program linear in receivers,
    not in D."""
    cap = max(LANES, GATHER_ELEMS // k // LANES * LANES)
    if interpret or d <= cap:
        return d
    return pl.cdiv(pl.cdiv(d, pl.cdiv(d, cap)), LANES) * LANES


def _gather_kernel(idx_ref, w_ref, *refs):
    k = len(refs) - 2
    slots, o_ref, t_ref = refs[:k], refs[k], refs[k + 1]
    r = pl.program_id(2)
    w = w_ref[...]
    v = jnp.concatenate([s[...] for s in slots], axis=0)
    o_ref[pl.ds(r, 1), :] = _row_dot(w, v)
    t_ref[pl.ds(r, 1), :] = _row_dot(w, jnp.ones((k, LANES), jnp.float32))


@functools.partial(jax.jit, static_argnames=("cols", "interpret"))
def segment_avg_gather(idx, w, table, cols, interpret=True):
    """idx [Bp, K] int32 rows of `table`, -1 where the slot is skipped
    (Bp % ROWS == 0, K % K_ALIGN == 0), w [Bp, K] f32 (0 wherever idx is
    -1), table [M, 1, D] f32 -> (sums [Bp, D], tot [Bp, LANES]) f32:
    sums[b] = w[b] · table[idx[b]], tot[b, :] = Σ_k w[b, k]."""
    bp, k = idx.shape
    d = table.shape[2]

    def slot_map(kk):
        def index(i, j, r, ix):
            row = ix[i * ROWS + r, kk]
            live = row >= 0
            return jnp.where(live, row, 0), 0, jnp.where(live, j, 0)
        return index

    in_specs = [pl.BlockSpec((None, 1, k),
                             lambda i, j, r, ix: (i * ROWS + r, 0, 0))]
    in_specs += [pl.BlockSpec((None, 1, cols), slot_map(kk))
                 for kk in range(k)]
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bp // ROWS, pl.cdiv(d, cols), ROWS),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((ROWS, cols),
                                    lambda i, j, r, ix: (i, j)),
                       pl.BlockSpec((ROWS, LANES),
                                    lambda i, j, r, ix: (i, 0))]),
        out_shape=[jax.ShapeDtypeStruct((bp, d), jnp.float32),
                   jax.ShapeDtypeStruct((bp, LANES), jnp.float32)],
        interpret=interpret,
        name="segment_avg_gather",
    )(idx, w.reshape(bp, 1, k), *([table] * k))


def _dequant_segment_avg_kernel(ws_ref, q_ref, o_ref):
    for r in range(ROWS):
        o_ref[r:r + 1, :] = _row_dot(ws_ref[r:r + 1, :],
                                     q_ref[r].astype(jnp.float32))


def _cols(dp: int, interpret: bool) -> int:
    """Feature-tile width of the int8 chunk kernel: COLS on hardware, one
    full-width tile in interpret mode (see `gather_cols`)."""
    return dp if interpret else COLS


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequant_segment_avg_chunk(ws, q, interpret=True):
    """ws [ROWS, K] f32 (weight*scale), q [ROWS, K, Dp] int8 -> [ROWS, Dp].

    Dequantize-and-reduce in one pass: the int8 payload tile is never
    written back to HBM as float32."""
    rows, k, dp = q.shape
    cols = _cols(dp, interpret)
    return pl.pallas_call(
        _dequant_segment_avg_kernel,
        grid=(dp // cols,),
        in_specs=[pl.BlockSpec((ROWS, k), lambda j: (0, 0)),
                  pl.BlockSpec((ROWS, k, cols), lambda j: (0, 0, j))],
        out_specs=pl.BlockSpec((ROWS, cols), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((rows, dp), jnp.float32),
        interpret=interpret,
    )(ws, q)
