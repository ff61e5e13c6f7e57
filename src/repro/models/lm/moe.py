"""Mixture-of-Experts LMs: mixtral-8x7b (8e top-2, SWA) and arctic-480b
(128e top-2 + dense residual FFN).

Dispatch is capacity-based sorted scatter (Switch-style, token-dropping):
  1. router softmax -> top-k experts + combine weights per token,
  2. assignments sorted by expert id; each expert processes a [C, D] buffer
     (C = capacity_factor * k * T / E, rounded up to a multiple of 8),
  3. expert GLU applied batched over experts via einsum [E, C, D] x [E, D, F],
  4. outputs scattered back and combined with router weights; dropped tokens
     (over capacity) fall through with zero contribution — the dense residual
     (arctic) or the residual stream still carries them.

A Switch-style load-balance auxiliary loss is returned alongside the layer
output and surfaces in the train metrics (weight cfg.router_aux_weight).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.constraints import (
    constrain_batch,
    constrain_logits,
    constrain_residual,
    gather_weights,
)
from repro.models.lm.config import ArchConfig
from repro.models.lm.dense import init_cache_dense
from repro.models.lm.layers import (
    _dense_init,
    apply_norm,
    attention,
    decode_attention,
    embed,
    init_attention,
    init_embedding,
    init_linear,
    init_mlp,
    init_norm,
    mlp,
    unembed,
)


def init_moe_ffn(rng, cfg: ArchConfig):
    k_r, k_g, k_u, k_d = jax.random.split(rng, 4)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    p = {
        "router": _dense_init(k_r, (d, e), d, jnp.float32),  # router in fp32
        "wg": _dense_init(k_g, (e, d, f), d, cfg.pdtype),
        "wu": _dense_init(k_u, (e, d, f), d, cfg.pdtype),
        "wd": _dense_init(k_d, (e, f, d), f, cfg.pdtype),
    }
    return p


def _capacity(cfg: ArchConfig, n_tokens: int) -> int:
    c = int(cfg.capacity_factor * cfg.top_k * n_tokens / cfg.n_experts) + 1
    return max(8, -(-c // 8) * 8)  # round up to multiple of 8


def moe_ffn(cfg: ArchConfig, p, x):
    """x [B,S,D] -> (out [B,S,D], aux_loss scalar).

    Dispatch strategy (cfg.moe_dispatch):
      "global"      — one capacity pool over all B*S tokens (baseline;
                      faithful single-host formulation, but on the sharded
                      mesh the [E, C_global, D] buffers cross the batch
                      sharding, which costs an fp32 all-reduce per layer).
      "batch_local" — the sorted dispatch runs per batch row
                      (vmap over B), so every data shard routes only its own
                      tokens and the expert buffers stay batch-sharded; no
                      cross-shard token motion.  Capacity is enforced per
                      row (same expected load)."""
    b, s, d = x.shape
    if cfg.moe_dispatch == "batch_local":
        return _moe_batch_local(cfg, p, x)
    out, aux = _moe_tokens(cfg, p, x.reshape(b * s, d))
    return out.reshape(b, s, d).astype(x.dtype), aux


def _moe_batch_local(cfg: ArchConfig, p, x):
    """Per-row sorted dispatch with an EXPLICIT batch dim kept data-sharded.

    Every tensor carries B as dim0 with a sharding constraint, so tokens
    never leave their data shard; the expert-weight contraction then cannot
    psum over data (the output is batch-sharded) and GSPMD is forced into the
    cheap per-layer weight all-gather instead."""
    b, s, d = x.shape
    k, e = cfg.top_k, cfg.n_experts
    xf = constrain_batch(x)

    logits = jnp.einsum("bsd,de->bse", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)  # [B,S,E]
    top_w, top_i = jax.lax.top_k(probs, k)  # [B,S,k]
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    onehot = jax.nn.one_hot(top_i, e, dtype=jnp.float32)  # [B,S,k,E]
    f_e = jnp.mean(onehot, axis=(0, 1, 2))  # fraction per expert
    p_e = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(f_e * p_e)  # same normalization as the global pool

    flat_e = top_i.reshape(b, s * k)  # [B, kS]
    order = jnp.argsort(flat_e, axis=1)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    counts = jnp.sum(onehot, axis=(1, 2)).astype(jnp.int32)  # [B,E]
    starts = jnp.cumsum(counts, axis=1) - counts  # [B,E]
    pos_in_e = (jnp.arange(s * k, dtype=jnp.int32)[None, :]
                - jnp.take_along_axis(starts, sorted_e, axis=1))
    cap = _capacity(cfg, s)
    keep = pos_in_e < cap
    slot = jnp.where(keep, sorted_e * cap + pos_in_e, e * cap)  # OOB drop
    tok = order // k  # [B,kS] source token per assignment
    bidx = jnp.arange(b, dtype=jnp.int32)[:, None]

    gathered = jnp.take_along_axis(
        xf.astype(cfg.adtype), tok[..., None], axis=1)  # [B,kS,D]
    buf = jnp.zeros((b, e * cap, d), cfg.adtype).at[bidx, slot].set(
        gathered, mode="drop")
    buf = constrain_batch(buf)
    h = buf.reshape(b, e, cap, d)
    gate = jax.nn.silu(jnp.einsum("becd,edf->becf", h, p["wg"].astype(h.dtype)))
    up = jnp.einsum("becd,edf->becf", h, p["wu"].astype(h.dtype))
    y = jnp.einsum("becf,efd->becd", gate * up, p["wd"].astype(h.dtype))
    y = constrain_batch(y.reshape(b, e * cap, d))

    w_sorted = (jnp.take_along_axis(top_w.reshape(b, s * k), order, axis=1)
                * keep.astype(jnp.float32))
    contrib = jnp.take_along_axis(
        y, jnp.minimum(slot, e * cap - 1)[..., None], axis=1)
    contrib = contrib.astype(jnp.float32) * w_sorted[..., None]
    out = jnp.zeros((b, s, d), jnp.float32).at[bidx, tok].add(contrib)
    return constrain_batch(out).astype(x.dtype), aux


def _moe_tokens(cfg: ArchConfig, p, xf):
    """Sorted capacity dispatch + expert GLU over a flat token block [T, D]."""
    t, d = xf.shape
    k = cfg.top_k
    e = cfg.n_experts

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)  # [T, E]
    top_w, top_i = jax.lax.top_k(probs, k)  # [T, k]
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    # Switch load-balance aux: E * Σ_e f_e * P_e
    f_e = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / (t * k)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)

    # ---- sorted capacity dispatch ----
    flat_e = top_i.reshape(-1)  # [kT] expert of each assignment
    order = jnp.argsort(flat_e)  # stable
    sorted_e = flat_e[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_e]
    cap = _capacity(cfg, t)
    keep = pos_in_e < cap
    slot = jnp.where(keep, sorted_e * cap + pos_in_e, e * cap)  # OOB -> dropped
    tok = order // k  # token index of each sorted assignment

    buf = jnp.zeros((e * cap, d), cfg.adtype).at[slot].set(
        xf[tok].astype(cfg.adtype), mode="drop")
    h = buf.reshape(e, cap, d)
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, p["wg"].astype(h.dtype)))
    up = jnp.einsum("ecd,edf->ecf", h, p["wu"].astype(h.dtype))
    y = jnp.einsum("ecf,efd->ecd", gate * up, p["wd"].astype(h.dtype))
    y = y.reshape(e * cap, d)

    w_sorted = top_w.reshape(-1)[order] * keep.astype(jnp.float32)
    contrib = jnp.take(y, jnp.minimum(slot, e * cap - 1), axis=0)
    contrib = contrib.astype(jnp.float32) * w_sorted[:, None]
    out = jnp.zeros((t, d), jnp.float32).at[tok].add(contrib)
    return out, aux


def init_layer_moe(rng, cfg: ArchConfig):
    k1, k2, k3 = jax.random.split(rng, 3)
    p = {
        "ln1": init_norm(cfg),
        "attn": init_attention(k1, cfg),
        "ln2": init_norm(cfg),
        "moe": init_moe_ffn(k2, cfg),
    }
    if cfg.dense_residual:
        p["dense_mlp"] = init_mlp(k3, cfg)
    return p


def init_moe_lm(rng, cfg: ArchConfig):
    k_emb, k_layers, k_unemb = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    params = {
        "embed": init_embedding(k_emb, cfg),
        "layers": jax.vmap(lambda k: init_layer_moe(k, cfg))(layer_keys),
        "final_norm": init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = init_linear(k_unemb, cfg.d_model, cfg.vocab, cfg)
    return params


def layer_apply_moe(cfg: ArchConfig, lp, x, positions):
    x = x + attention(cfg, lp["attn"], apply_norm(cfg, x, lp["ln1"]), positions)
    h = apply_norm(cfg, x, lp["ln2"])
    y, aux = moe_ffn(cfg, lp["moe"], h)
    if cfg.dense_residual:
        y = y + mlp(cfg, lp["dense_mlp"], h)
    return x + y, aux


def forward_moe(cfg: ArchConfig, params, tokens, positions=None):
    """tokens [B,S] -> (logits [B,S,V], aux_loss)."""
    x = constrain_batch(embed(cfg, params["embed"], tokens))
    if positions is None:
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)

    def body(h, lp):
        h = constrain_residual(h, cfg.residual_shard)
        if cfg.zero3_gather:
            lp = gather_weights(lp)
        h, aux = layer_apply_moe(cfg, lp, h, positions)
        return h, aux

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, auxs = jax.lax.scan(body_fn, x, params["layers"])
    x = apply_norm(cfg, x, params["final_norm"])
    logits = constrain_logits(unembed(cfg, params.get("unembed"), params["embed"], x))
    return logits, jnp.mean(auxs)


init_cache_moe = init_cache_dense


def decode_step_moe(cfg: ArchConfig, params, cache, tokens):
    x = embed(cfg, params["embed"], tokens)
    length = cache["length"]

    def body(h, inp):
        lp, lc = inp
        a, lc_new = decode_attention(
            cfg, lp["attn"], apply_norm(cfg, h, lp["ln1"]), lc, length)
        h = h + a
        hn = apply_norm(cfg, h, lp["ln2"])
        y, _ = moe_ffn(cfg, lp["moe"], hn)
        if cfg.dense_residual:
            y = y + mlp(cfg, lp["dense_mlp"], hn)
        return h + y, lc_new

    layer_caches = {"k": cache["k"], "v": cache["v"], "slot_pos": cache["slot_pos"]}
    x, new_caches = jax.lax.scan(body, x, (params["layers"], layer_caches))
    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params.get("unembed"), params["embed"], x)
    return logits, dict(new_caches, length=length + 1)
