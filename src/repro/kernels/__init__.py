"""Pallas TPU kernels for the paper's compute hot-spots.

  decdiff_update — fused global-L2 + attenuated step (Eq. 5) over the
                   flattened model (two streaming passes, block reductions)
  vt_kl_loss     — fused virtual-teacher KL over the vocab axis (Eq. 8),
                   closed form, custom_vjp with fused softmax-p_t backward
  neighbor_avg   — weighted average of stacked neighbour models (Eq. 6)
  dequant_avg    — fused int8-dequantize + weighted average (Eq. 6 applied
                   directly to the comm layer's quantized gossip payloads;
                   single-receiver and receiver-block variants — the block
                   form is what the shard_map DFL round runs on the
                   all_gathered payload)
  decode_attention — fused one-token GQA attention over the ring KV cache
                   (the serving hot spot; online softmax over cache tiles)

`ops` holds the jit'd public wrappers (compiled on TPU, interpreted on CPU);
`ref` holds the pure-jnp oracles the tests sweep against.
"""
from repro.kernels.ops import (  # noqa: F401
    decdiff_update,
    decdiff_update_tree,
    decode_attention_fused,
    dequant_neighbor_avg,
    dequant_neighbor_avg_rows,
    neighbor_avg,
    vt_kl_loss_fused,
)
