"""Ragged segment neighbor-average kernels (the sparse engine's reduce).

`neighbor_avg` / `dequant_neighbor_avg_rows` assume one dense `[N, D]` /
`[R, N]` weight panel — O(N^2) state.  The sparse engine instead gathers
each receiver's neighbour rows into slot-padded blocks `[B, K, D]` (K =
bucket width, degree-dependent) and reduces them here.

Bitwise contract: each receiver row is contracted by its OWN unrolled
`(1, K) · (K, cols)` GEMV inside the kernel body.  A batched contraction's
bits depend on the batch geometry (probed: `einsum("bk,bkd->bd")` at B=100
differs from the same rows at B=1), so per-row unrolling is what makes the
result invariant to how receivers are blocked into chunks, pods, or degree
buckets — the property the dense-oracle equivalence rests on.  Zero-weight
tail slots (padding, undelivered edges) are bit-neutral for any finite
slot values: a `0.0 * x` term adds ±0.0, which never perturbs an IEEE
accumulator.

Callers drive fixed `[ROWS, K, D]` chunks through `lax.map` (see
`repro.kernels.ops.segment_neighbor_avg`): the kernel traces once per
shape, so interpret mode stays cheap even at 10^4-10^6 total receivers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 8  # receiver rows per chunk (fixed so every call shares one geometry)
COLS = 256  # feature columns per grid tile
K_ALIGN = 8  # callers zero-pad the slot axis K to a multiple of this


def _row_dot(w, v):
    """One receiver row's own contraction: (1, K) · (K, cols) -> (1, cols).

    Rank-2 on both sides, because Mosaic lowers no rank-1 dot; HIGHEST so
    the MXU keeps fp32 accuracy."""
    return jnp.dot(w, v, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _segment_avg_kernel(w_ref, v_ref, o_ref):
    for r in range(ROWS):
        o_ref[r:r + 1, :] = _row_dot(w_ref[r:r + 1, :], v_ref[r])


def _dequant_segment_avg_kernel(ws_ref, q_ref, o_ref):
    for r in range(ROWS):
        o_ref[r:r + 1, :] = _row_dot(ws_ref[r:r + 1, :],
                                     q_ref[r].astype(jnp.float32))


def _cols(dp: int, interpret: bool) -> int:
    """Feature-tile width.  On hardware the COLS grid bounds VMEM; in
    interpret mode every grid point unrolls into the caller's trace, so one
    full-width tile keeps the program linear in ROWS, not in D.  Column
    tiling cannot change bits either way: each output element accumulates
    over the K axis only, so its addition order is tile-independent."""
    return dp if interpret else COLS


@functools.partial(jax.jit, static_argnames=("interpret",))
def segment_avg_chunk(w, v, interpret=True):
    """w [ROWS, K] f32, v [ROWS, K, Dp] f32 (Dp % COLS == 0) -> [ROWS, Dp]."""
    rows, k, dp = v.shape
    cols = _cols(dp, interpret)
    return pl.pallas_call(
        _segment_avg_kernel,
        grid=(dp // cols,),
        in_specs=[pl.BlockSpec((ROWS, k), lambda j: (0, 0)),
                  pl.BlockSpec((ROWS, k, cols), lambda j: (0, 0, j))],
        out_specs=pl.BlockSpec((ROWS, cols), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((rows, dp), jnp.float32),
        interpret=interpret,
    )(w, v)


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
