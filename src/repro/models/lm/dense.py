"""Dense decoder-only LM (llama/qwen family), scan-over-layers.

Covers: deepseek-7b (llama arch), qwen1.5-0.5b / qwen2.5-14b (QKV bias),
qwen3-32b (qk-norm, GQA, head_dim 128) — and serves as the text trunk for
llava (vlm.py).
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


def init_layer(rng, cfg: ArchConfig):
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": init_norm(cfg),
        "attn": init_attention(k1, cfg),
        "ln2": init_norm(cfg),
        "mlp": init_mlp(k2, cfg),
    }


def init_dense(rng, cfg: ArchConfig):
    k_emb, k_layers, k_unemb = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    params = {
        "embed": init_embedding(k_emb, cfg),
        "layers": jax.vmap(lambda k: init_layer(k, cfg))(layer_keys),
        "final_norm": init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = init_linear(k_unemb, cfg.d_model, cfg.vocab, cfg)
    return params


def layer_apply(cfg: ArchConfig, lp, x, positions):
    x = x + attention(cfg, lp["attn"], apply_norm(cfg, x, lp["ln1"]), positions)
    x = x + mlp(cfg, lp["mlp"], apply_norm(cfg, x, lp["ln2"]))
    return x


def trunk(cfg: ArchConfig, params, x, positions):
    """Run the scanned layer stack on embedded input x [B,S,D]."""

    def body(h, lp):
        h = constrain_residual(h, cfg.residual_shard)
        if cfg.zero3_gather:
            lp = gather_weights(lp)
        return layer_apply(cfg, lp, h, positions), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_fn, x, params["layers"])
    return apply_norm(cfg, x, params["final_norm"])


def forward_dense(cfg: ArchConfig, params, tokens, positions=None):
    """tokens [B,S] -> logits [B,S,V]."""
    x = constrain_batch(embed(cfg, params["embed"], tokens))
    if positions is None:
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    x = trunk(cfg, params, x, positions)
    return constrain_logits(unembed(cfg, params.get("unembed"), params["embed"], x))


def init_cache_dense(cfg: ArchConfig, batch: int, seq_len: int):
    window = seq_len if cfg.decode_window is None else min(cfg.decode_window, seq_len)
    if cfg.sliding_window is not None:
        window = min(window, cfg.sliding_window)
    spec = CacheSpec(batch=batch, window=window, n_kv_heads=cfg.n_kv_heads,
                     head_dim=cfg.head_dim, dtype=cfg.activation_dtype)
    return init_kv_cache(spec, cfg.n_layers)


def decode_step_dense(cfg: ArchConfig, params, cache, tokens):
    """tokens [B,1] -> (logits [B,1,V], cache)."""
    x = embed(cfg, params["embed"], tokens)
    length = cache["length"]

    def body(h, inp):
        lp, lc = inp
        a, lc_new = decode_attention(
            cfg, lp["attn"], apply_norm(cfg, h, lp["ln1"]), lc, length)
        h = h + a
        h = h + mlp(cfg, lp["mlp"], apply_norm(cfg, h, lp["ln2"]))
        return h, lc_new

    layer_caches = {"k": cache["k"], "v": cache["v"], "slot_pos": cache["slot_pos"]}
    x, new_caches = jax.lax.scan(body, x, (params["layers"], layer_caches))
    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params.get("unembed"), params["embed"], x)
    new_cache = dict(new_caches, length=length + 1)
    return logits, new_cache
