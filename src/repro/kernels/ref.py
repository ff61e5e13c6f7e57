"""Pure-jnp oracles for the Pallas kernels (tests assert_allclose vs these)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def vt_kl_loss_ref(logits: jnp.ndarray, labels: jnp.ndarray, beta: float):
    """Eq. 8 mean KL(p_t || softmax(z)) with the materialized teacher."""
    z = logits.astype(jnp.float32)
    v = z.shape[-1]
    a = (1.0 - beta) / (v - 1)
    onehot = jax.nn.one_hot(labels, v, dtype=jnp.float32)
    p_t = onehot * beta + (1.0 - onehot) * a
    logp = jax.nn.log_softmax(z, axis=-1)
    log_pt = jnp.log(jnp.maximum(p_t, 1e-30))
    return jnp.mean(jnp.sum(p_t * (log_pt - logp), axis=-1))


def vt_kl_grad_ref(logits: jnp.ndarray, labels: jnp.ndarray, beta: float):
    """d(mean KL)/d logits = (softmax(z) - p_t) / n_rows."""
    z = logits.astype(jnp.float32)
    v = z.shape[-1]
    a = (1.0 - beta) / (v - 1)
    onehot = jax.nn.one_hot(labels, v, dtype=jnp.float32)
    p_t = onehot * beta + (1.0 - onehot) * a
    p = jax.nn.softmax(z, axis=-1)
    return (p - p_t) / z.shape[0]


def dequant_neighbor_avg_rows_ref(q: jnp.ndarray, scales: jnp.ndarray,
                                  wn: jnp.ndarray):
    """Multi-receiver Eq. 6 over int8 payloads: dequantize, then apply each
    receiver's (pre-normalized) weight row."""
    dq = q.astype(jnp.float32) * scales.astype(jnp.float32)[:, None]
    return jnp.einsum("rn,nd->rd", wn.astype(jnp.float32), dq)
