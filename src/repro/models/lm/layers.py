"""Shared transformer building blocks (pure JAX, scan-over-layers friendly).

Everything here is a pure function over plain dict params.  Per-layer params
are created by `init_*` for ONE layer; the model builders stack L layers by
vmapping the init over per-layer keys, which yields [L, ...] leaves that
`jax.lax.scan` consumes — keeping the lowered HLO size independent of depth.

Attention supports MHA/GQA, RoPE, qk-norm (qwen3), QKV bias (qwen1.5/2.5),
causal / non-causal / sliding-window masks, and two execution paths:

  * plain  — materialized [Sq, Sk] scores; used when S <= full_attn_max_seq.
  * chunked — flash-style online-softmax scan over (q-chunk, kv-chunk) pairs;
    memory O(qc * kvc) instead of O(S^2).  Used for long prefill.

Decode uses a ring-buffer KV cache (absolute positions tracked per slot) so a
sliding-window config keeps only `window` slots even at 500k context.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.lm.config import ArchConfig

# ---------------------------------------------------------------- norms


def rms_norm(x, scale, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def apply_norm(cfg: ArchConfig, x, p):
    if cfg.norm == "rmsnorm":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"])


def init_norm(cfg: ArchConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), cfg.pdtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), cfg.pdtype)
    return p


# ---------------------------------------------------------------- rotary


def rope_frequencies(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [..., S, H, hd]; positions: broadcastable to [..., S]."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)  # [hd/2]
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., S, hd/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., S, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : hd // 2], x32[..., hd // 2 :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- linear


def _dense_init(rng, shape, fan_in: int, dtype):
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(rng, shape, jnp.float32) * std).astype(dtype)


def init_linear(rng, d_in: int, d_out: int, cfg: ArchConfig, bias: bool = False):
    k1, _ = jax.random.split(rng)
    p = {"w": _dense_init(k1, (d_in, d_out), d_in, cfg.pdtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), cfg.pdtype)
    return p


def linear(x, p):
    y = jnp.einsum("...d,df->...f", x, p["w"].astype(x.dtype))
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------- attention


def init_attention(rng, cfg: ArchConfig):
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    d = cfg.d_model
    p = {
        "wq": init_linear(k1, d, cfg.q_dim, cfg, bias=cfg.qkv_bias),
        "wk": init_linear(k2, d, cfg.kv_dim, cfg, bias=cfg.qkv_bias),
        "wv": init_linear(k3, d, cfg.kv_dim, cfg, bias=cfg.qkv_bias),
        "wo": init_linear(k4, cfg.q_dim, d, cfg, bias=False),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((cfg.head_dim,), cfg.pdtype)}
        p["k_norm"] = {"scale": jnp.ones((cfg.head_dim,), cfg.pdtype)}
    return p


def _project_qkv(cfg: ArchConfig, p, x, positions, rope: bool):
    b, s, _ = x.shape
    q = linear(x, p["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = linear(x, p["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = linear(x, p["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"]["scale"])
        k = rms_norm(k, p["k_norm"]["scale"])
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mask_bias(q_pos, k_pos, causal: bool, window: Optional[int], dtype=jnp.float32):
    """Additive mask bias [..., Sq, Sk] from absolute positions."""
    ok = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]), bool)
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    if causal:
        ok = ok & (kp <= qp)
    if window is not None:
        ok = ok & (kp > qp - window)
    return jnp.where(ok, 0.0, -1e30).astype(dtype)


def _gqa_scores(q, k, scale):
    """q: [B,Sq,H,hd], k: [B,Sk,K,hd] -> scores [B,K,G,Sq,Sk]."""
    b, sq, h, hd = q.shape
    kk = k.shape[2]
    g = h // kk
    qg = q.reshape(b, sq, kk, g, hd)
    return jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                      k.astype(jnp.float32)) * scale


def _gqa_combine(probs, v):
    """probs: [B,K,G,Sq,Sk], v: [B,Sk,K,hd] -> [B,Sq,H,hd]."""
    b, kk, g, sq, sk = probs.shape
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(probs.dtype))
    return out.reshape(b, sq, kk * g, v.shape[-1])


def _plain_attention(cfg, q, k, v, q_pos, k_pos, causal, window):
    scale = 1.0 / math.sqrt(cfg.head_dim)
    scores = _gqa_scores(q, k, scale)  # [B,K,G,Sq,Sk] fp32
    bias = _mask_bias(q_pos, k_pos, causal, window)  # [Sq,Sk], broadcasts
    probs = jax.nn.softmax(scores + bias, axis=-1)
    if cfg.attn_probs_bf16:
        probs = probs.astype(jnp.bfloat16)  # halve the S^2 traffic
    return _gqa_combine(probs, v).astype(q.dtype)


def _chunked_attention(cfg, q, k, v, q_pos, k_pos, causal, window):
    """Flash-style two-level scan with online softmax.

    Baseline computes every (q-chunk, kv-chunk) pair and relies on masking for
    causality (fully-masked pairs are wasted FLOPs)."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    kk = cfg.n_kv_heads
    g = h // kk
    qc = min(cfg.attn_chunk_q, sq)
    kc = min(cfg.attn_chunk_kv, sk)
    assert sq % qc == 0 and sk % kc == 0, (sq, qc, sk, kc)
    nq, nk = sq // qc, sk // kc
    scale = 1.0 / math.sqrt(hd)

    qr = q.reshape(b, nq, qc, h, hd).transpose(1, 0, 2, 3, 4)  # [nq,B,qc,H,hd]
    qpr = q_pos.reshape(nq, qc)
    kr = k.reshape(b, nk, kc, kk, hd).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(b, nk, kc, kk, hd).transpose(1, 0, 2, 3, 4)
    kpr = k_pos.reshape(nk, kc)

    def q_body(_, q_in):
        qi, qp = q_in  # [B,qc,H,hd], [qc]
        qg = qi.reshape(b, qc, kk, g, hd)

        def kv_body(carry, kv_in):
            m, l, acc = carry
            ki, vi, kp = kv_in
            s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                           ki.astype(jnp.float32)) * scale
            s = s + _mask_bias(qp, kp, causal, window)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vi.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kk, g, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, kk, g, qc), jnp.float32)
        a0 = jnp.zeros((b, kk, g, qc, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0), (kr, vr, kpr))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, qc, h, hd)  # [B,qc,H,hd]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_body, None, (qr, qpr))  # [nq,B,qc,H,hd]
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)


def attention(cfg: ArchConfig, p, x, positions=None, *, causal: bool = True,
              rope: bool = True, kv_override=None):
    """Self- (or cross-, via kv_override) attention over a full sequence.

    kv_override: optional (k, v, k_pos) for cross-attention (whisper decoder).
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)
    q, k, v = _project_qkv(cfg, p, x, positions, rope)
    q_pos = positions
    if kv_override is not None:
        k, v, k_pos = kv_override
    else:
        k_pos = positions
    sk = k.shape[1]
    if max(s, sk) <= cfg.full_attn_max_seq:
        out = _plain_attention(cfg, q, k, v, q_pos, k_pos, causal, cfg.sliding_window)
    else:
        out = _chunked_attention(cfg, q, k, v, q_pos, k_pos, causal, cfg.sliding_window)
    return linear(out.reshape(b, s, cfg.q_dim), p["wo"])


def cross_kv(cfg: ArchConfig, p, enc_out):
    """Precompute cross-attention K/V from encoder output (whisper decode)."""
    b, s, _ = enc_out.shape
    k = linear(enc_out, p["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = linear(enc_out, p["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"]["scale"])
    return k, v


# ------------------------------------------------- decode (ring KV cache)


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    batch: int
    window: int  # number of cache slots (= seq_len, or SWA window)
    n_kv_heads: int
    head_dim: int
    dtype: str = "bfloat16"


def init_kv_cache(spec: CacheSpec, n_layers: int):
    z = lambda: jnp.zeros((n_layers, spec.batch, spec.window, spec.n_kv_heads,
                           spec.head_dim), jnp.dtype(spec.dtype))
    return {
        "k": z(),
        "v": z(),
        "slot_pos": jnp.full((n_layers, spec.window), -1, jnp.int32),
        "length": jnp.zeros((), jnp.int32),  # absolute position of next token
    }


def decode_attention(cfg: ArchConfig, p, x, layer_cache, length):
    """One-token attention against a ring-buffer cache.

    x: [B, 1, D]; layer_cache: dict(k,v [B,W,K,hd], slot_pos [W]).
    Returns (out [B,1,D], updated layer_cache).
    """
    b = x.shape[0]
    pos = length  # scalar int32, absolute position of the new token
    q, k_new, v_new = _project_qkv(cfg, p, x, pos[None].astype(jnp.int32), True)
    w = layer_cache["k"].shape[1]
    slot = (pos % w).astype(jnp.int32)
    k_cache = layer_cache["k"].at[:, slot].set(k_new[:, 0])
    v_cache = layer_cache["v"].at[:, slot].set(v_new[:, 0])
    slot_pos = layer_cache["slot_pos"].at[slot].set(pos.astype(jnp.int32))

    scale = 1.0 / math.sqrt(cfg.head_dim)
    scores = _gqa_scores(q, k_cache, scale)  # [B,K,G,1,W]
    valid = slot_pos >= 0
    ok = valid & (slot_pos <= pos)
    if cfg.sliding_window is not None:
        ok = ok & (slot_pos > pos - cfg.sliding_window)
    bias = jnp.where(ok, 0.0, -1e30).astype(scores.dtype)  # [W]
    probs = jax.nn.softmax(scores + bias, axis=-1)
    out = _gqa_combine(probs, v_cache).astype(x.dtype)  # [B,1,H,hd]
    out = linear(out.reshape(b, 1, cfg.q_dim), p["wo"])
    return out, {"k": k_cache, "v": v_cache, "slot_pos": slot_pos}


# ---------------------------------------------------------------- MLP


def init_mlp(rng, cfg: ArchConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.act == "silu":
        k1, k2, k3 = jax.random.split(rng, 3)
        return {
            "wg": init_linear(k1, d, f, cfg),
            "wu": init_linear(k2, d, f, cfg),
            "wd": init_linear(k3, f, d, cfg),
        }
    k1, k2 = jax.random.split(rng)
    return {"w1": init_linear(k1, d, f, cfg, bias=True),
            "w2": init_linear(k2, f, d, cfg, bias=True)}


def mlp(cfg: ArchConfig, p, x):
    if cfg.act == "silu":
        return linear(jax.nn.silu(linear(x, p["wg"])) * linear(x, p["wu"]), p["wd"])
    return linear(jax.nn.gelu(linear(x, p["w1"])), p["w2"])


# ---------------------------------------------------------------- embeddings


def init_embedding(rng, cfg: ArchConfig):
    emb = (jax.random.normal(rng, (cfg.vocab, cfg.d_model), jnp.float32) * 0.02
           ).astype(cfg.pdtype)
    return {"table": emb}


def embed(cfg: ArchConfig, p, tokens):
    return jnp.take(p["table"].astype(cfg.adtype), tokens, axis=0)


def unembed(cfg: ArchConfig, p_unemb, p_emb, x):
    if cfg.tie_embeddings:
        w = p_emb["table"].astype(x.dtype).T
    else:
        w = p_unemb["w"].astype(x.dtype)
    return jnp.einsum("...d,dv->...v", x, w)
