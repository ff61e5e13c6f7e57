"""Zamba2-style hybrid: a Mamba2 backbone with a single SHARED attention
block invoked periodically (arXiv:2411.15242).

Layout: n_layers mamba2 layers, grouped into n_layers/shared_attn_every
groups; after each group the one shared transformer block (attention + MLP,
one set of weights reused at every invocation) runs on the concatenation of
the current hidden state and the original embedding (projected 2D -> D), as
in the Zamba family.  We omit the per-invocation LoRA deltas on the shared
block (noted in DESIGN.md).

Decode carries the mamba recurrent states of every layer plus ONE ring-buffer
KV cache for the shared block (its invocations all share the cache — each
invocation sees the shared block's own past, which is the Zamba2 semantics of
a shared module with shared KV... we keep one cache per *invocation group* to
preserve causal consistency: [G, B, W, K, hd]).
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
from repro.models.lm.layers import (
    CacheSpec,
    apply_norm,
    attention,
    decode_attention,
    embed,
    init_attention,
    init_embedding,
    init_kv_cache,
    init_linear,
    init_mlp,
    init_norm,
    mlp,
    unembed,
)
from repro.models.lm.ssm import (
    init_cache_ssm,
    init_ssm_layer,
    ssm_block,
    ssm_decode_block,
)


def _n_groups(cfg: ArchConfig) -> int:
    assert cfg.shared_attn_every > 0 and cfg.n_layers % cfg.shared_attn_every == 0
    return cfg.n_layers // cfg.shared_attn_every


def init_hybrid_lm(rng, cfg: ArchConfig):
    k_emb, k_layers, k_shared, k_proj, k_unemb = jax.random.split(rng, 5)
    g = _n_groups(cfg)
    e = cfg.shared_attn_every
    layer_keys = jax.random.split(k_layers, cfg.n_layers).reshape(g, e, -1)
    k1, k2 = jax.random.split(k_shared)
    return {
        "embed": init_embedding(k_emb, cfg),
        # [G, E, ...] stacked mamba layers: outer python loop over groups,
        # inner scan over the e layers of each group.
        "mamba": jax.vmap(jax.vmap(lambda k: init_ssm_layer(k, cfg)))(layer_keys),
        "shared": {
            "in_proj": init_linear(k_proj, 2 * cfg.d_model, cfg.d_model, cfg),
            "ln1": init_norm(cfg),
            "attn": init_attention(k1, cfg),
            "ln2": init_norm(cfg),
            "mlp": init_mlp(k2, cfg),
        },
        "final_norm": init_norm(cfg),
        "unembed": init_linear(k_unemb, cfg.d_model, cfg.vocab, cfg),
    }


def _shared_block(cfg: ArchConfig, sp, x, x0, positions):
    h = jnp.concatenate([x, x0], axis=-1)
    h = jnp.einsum("...f,fd->...d", h, sp["in_proj"]["w"].astype(h.dtype))
    h = h + attention(cfg, sp["attn"], apply_norm(cfg, h, sp["ln1"]), positions)
    h = h + mlp(cfg, sp["mlp"], apply_norm(cfg, h, sp["ln2"]))
    return x + h


def forward_hybrid(cfg: ArchConfig, params, tokens, positions=None):
    x = constrain_batch(embed(cfg, params["embed"], tokens))
    x0 = x
    if positions is None:
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    g = _n_groups(cfg)

    def group_scan(h, group_layers):
        def body(hh, lp):
            hh = constrain_residual(hh, cfg.residual_shard)
            if cfg.zero3_gather:
                lp = gather_weights(lp)
            return ssm_block(cfg, lp, hh), None

        body_fn = jax.checkpoint(body) if cfg.remat else body
        h, _ = jax.lax.scan(body_fn, h, group_layers)
        return h

    for gi in range(g):
        group_layers = jax.tree.map(lambda p, _gi=gi: p[_gi], params["mamba"])
        x = group_scan(x, group_layers)
        x = constrain_batch(_shared_block(cfg, params["shared"], x, x0, positions))
    x = apply_norm(cfg, x, params["final_norm"])
    return constrain_logits(unembed(cfg, params.get("unembed"), params["embed"], x))


def init_cache_hybrid(cfg: ArchConfig, batch: int, seq_len: int):
    ssm_cache = init_cache_ssm(cfg, batch, seq_len)
    g = _n_groups(cfg)
    window = seq_len if cfg.decode_window is None else min(cfg.decode_window, seq_len)
    spec = CacheSpec(batch=batch, window=window, n_kv_heads=cfg.n_kv_heads,
                     head_dim=cfg.head_dim, dtype=cfg.activation_dtype)
    attn_cache = init_kv_cache(spec, g)  # one cache per invocation group
    return {
        "conv": ssm_cache["conv"], "state": ssm_cache["state"],
        "attn_k": attn_cache["k"], "attn_v": attn_cache["v"],
        "attn_slot_pos": attn_cache["slot_pos"],
        "length": jnp.zeros((), jnp.int32),
    }


def decode_step_hybrid(cfg: ArchConfig, params, cache, tokens):
    x = embed(cfg, params["embed"], tokens)[:, 0]  # [B,D]
    x0 = x
    g = _n_groups(cfg)
    e = cfg.shared_attn_every
    length = cache["length"]

    conv_all = cache["conv"].reshape(g, e, *cache["conv"].shape[1:])
    state_all = cache["state"].reshape(g, e, *cache["state"].shape[1:])
    new_conv, new_state = [], []
    new_k, new_v, new_sp = [], [], []

    for gi in range(g):
        group_layers = jax.tree.map(lambda p, _gi=gi: p[_gi], params["mamba"])

        def body(h, inp):
            lp, conv_c, st = inp
            h, conv_n, st_n = ssm_decode_block(cfg, lp, h, conv_c, st)
            return h, (conv_n, st_n)

        x, (conv_n, state_n) = jax.lax.scan(
            body, x, (group_layers, conv_all[gi], state_all[gi]))
        new_conv.append(conv_n)
        new_state.append(state_n)
        # shared attention block on the single token
        sp = params["shared"]
        hcat = jnp.concatenate([x, x0], axis=-1)[:, None, :]
        h = jnp.einsum("...f,fd->...d", hcat, sp["in_proj"]["w"].astype(hcat.dtype))
        lc = {"k": cache["attn_k"][gi], "v": cache["attn_v"][gi],
              "slot_pos": cache["attn_slot_pos"][gi]}
        a, lc_new = decode_attention(cfg, sp["attn"],
                                     apply_norm(cfg, h, sp["ln1"]), lc, length)
        h = h + a
        h = h + mlp(cfg, sp["mlp"], apply_norm(cfg, h, sp["ln2"]))
        x = x + h[:, 0]
        new_k.append(lc_new["k"])
        new_v.append(lc_new["v"])
        new_sp.append(lc_new["slot_pos"])

    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params.get("unembed"), params["embed"], x[:, None, :])
    new_cache = {
        "conv": jnp.stack(new_conv).reshape(cfg.n_layers, *cache["conv"].shape[1:]),
        "state": jnp.stack(new_state).reshape(cfg.n_layers, *cache["state"].shape[1:]),
        "attn_k": jnp.stack(new_k), "attn_v": jnp.stack(new_v),
        "attn_slot_pos": jnp.stack(new_sp),
        "length": length + 1,
    }
    return logits, new_cache
