"""Pallas TPU kernels.

  segment_avg    — the neighbour reduce every engine round runs:
                   `segment_avg_gather` DMAs each receiver's neighbour rows
                   from a table by scalar-prefetched ids and contracts them
                   under the gossip weights (behind
                   `ops.segment_neighbor_avg_rows` and the panel form
                   `ops.segment_neighbor_avg`); `dequant_segment_avg_chunk`
                   is its int8 form (`ops.dequant_segment_neighbor_avg`)
  vt_kl_loss     — fused virtual-teacher KL over the vocab axis (Eq. 8),
                   closed form, custom_vjp with fused softmax-p_t backward
  dequant_avg    — fused int8-dequantize + weighted average for a block of
                   receivers (Eq. 6 on the comm layer's quantized payloads;
                   what the legacy shard_map LM round in `repro.dist`
                   runs on the all_gathered payload)

`ops` holds the jit'd public wrappers (compiled on TPU, interpreted on CPU);
`ref` holds the pure-jnp oracles the tests sweep against.
"""
from repro.kernels.ops import (  # noqa: F401
    dequant_neighbor_avg_rows,
    vt_kl_loss_fused,
)
