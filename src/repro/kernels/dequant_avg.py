"""Pallas kernel: fused int8-dequantize + weighted neighbour average for a
block of receivers.

The comm layer's int8 gossip hands each receiver N quantized neighbour rows
(q [N, D] int8, one fp32 scale per row).  Materializing the dequantized
fp32 models in HBM would cost 4x the payload's footprint and a full extra
HBM round-trip; instead this kernel folds the dequantization into the
Eq. 6 reduction:

    out[r, d] = Σ_n (wn[r, n] * scale[n]) * q[n, d]

D streams in (N, COLS) tiles — int8 rows are 4x denser per tile, so one
tile = N*2048 bytes — and the per-sender weight*scale product folds into
the weight matrix, keeping the inner loop one int8->fp32 matrix product.

Weights are pre-normalized by the caller (ops.dequant_neighbor_avg_rows).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

COLS = 2048


def _dequant_avg_rows_kernel(q_ref, ws_ref, out_ref):
    # ws[r, n] = wn[r, n] * scale[n]: per-receiver weights with the senders'
    # dequantization scales folded in, so the whole Eq. 6 block reduces to
    # one int8->fp32 matrix product per tile on the MXU.
    out_ref[...] = jnp.einsum(
        "rn,nd->rd", ws_ref[...], q_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)


def dequant_avg_rows_blocks(q: jnp.ndarray, ws: jnp.ndarray, *,
                            interpret: bool = False) -> jnp.ndarray:
    """The multi-receiver variant the shard_map round uses: every receiver
    in a pod block averages the SAME N gathered int8 payloads under its own
    weight row.

    q [N, D] int8 (the all_gathered wire payloads), ws [R, N] fp32
    (= row-normalized gossip weights x per-sender scales) -> [R, D] fp32
    weighted dequantized averages.  Each q tile is loaded once and reused
    for all R receivers, which is the point of fusing across the block.
    """
    n, d = q.shape
    r = ws.shape[0]
    assert d % COLS == 0, d
    return pl.pallas_call(
        _dequant_avg_rows_kernel,
        grid=(d // COLS,),
        in_specs=[
            pl.BlockSpec((n, COLS), lambda i: (0, i)),
            pl.BlockSpec((r, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((r, COLS), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((r, d), jnp.float32),
        interpret=interpret,
    )(q, ws)
