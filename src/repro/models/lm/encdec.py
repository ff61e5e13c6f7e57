"""Whisper-style encoder-decoder (arXiv:2212.04356) — transformer backbone.

Per the assignment carve-out, the mel-spectrogram + conv feature extractor is
a STUB: the model consumes precomputed frame embeddings [B, S_enc, D] (S_enc
= seq_len / enc_seq_divisor, standing in for the conv stride-2 downsampling).
We use RoPE instead of Whisper's learned absolute positions so decode can run
at arbitrary context lengths (500k test) — a documented TPU-era adaptation
that leaves the enc-dec attention structure intact.

Whisper uses LayerNorm + GELU MLPs + MHA (20 heads, kv=20); the decoder adds
cross-attention to the encoder output.  Decode caches: ring-buffer self-attn
KV per decoder layer + precomputed cross-attn K/V per decoder layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.constraints import (
    constrain_logits,
    constrain_residual,
    gather_weights,
)
from repro.models.lm.config import ArchConfig
from repro.models.lm.layers import (
    CacheSpec,
    apply_norm,
    attention,
    cross_kv,
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


def init_enc_layer(rng, cfg: ArchConfig):
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": init_norm(cfg),
        "attn": init_attention(k1, cfg),
        "ln2": init_norm(cfg),
        "mlp": init_mlp(k2, cfg),
    }


def init_dec_layer(rng, cfg: ArchConfig):
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "ln1": init_norm(cfg),
        "self_attn": init_attention(k1, cfg),
        "ln_x": init_norm(cfg),
        "cross_attn": init_attention(k2, cfg),
        "ln2": init_norm(cfg),
        "mlp": init_mlp(k3, cfg),
    }


def init_encdec(rng, cfg: ArchConfig):
    k_emb, k_enc, k_dec, k_unemb = jax.random.split(rng, 4)
    enc_keys = jax.random.split(k_enc, cfg.n_enc_layers)
    dec_keys = jax.random.split(k_dec, cfg.n_layers)
    return {
        "embed": init_embedding(k_emb, cfg),  # decoder token embeddings
        "enc_layers": jax.vmap(lambda k: init_enc_layer(k, cfg))(enc_keys),
        "enc_final_norm": init_norm(cfg),
        "dec_layers": jax.vmap(lambda k: init_dec_layer(k, cfg))(dec_keys),
        "final_norm": init_norm(cfg),
        "unembed": init_linear(k_unemb, cfg.d_model, cfg.vocab, cfg),
    }


def encode(cfg: ArchConfig, params, enc_embeds):
    """Stub-frontend encoder: enc_embeds [B, S_enc, D] -> [B, S_enc, D]."""
    s_enc = enc_embeds.shape[1]
    positions = jnp.arange(s_enc, dtype=jnp.int32)
    x = enc_embeds.astype(cfg.adtype)

    def body(h, lp):
        h = constrain_residual(h, cfg.residual_shard)
        if cfg.zero3_gather:
            lp = gather_weights(lp)
        h = h + attention(cfg, lp["attn"], apply_norm(cfg, h, lp["ln1"]),
                          positions, causal=False)
        h = h + mlp(cfg, lp["mlp"], apply_norm(cfg, h, lp["ln2"]))
        return h, None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_fn, x, params["enc_layers"])
    return apply_norm(cfg, x, params["enc_final_norm"])


def decode_train(cfg: ArchConfig, params, tokens, enc_out):
    """Teacher-forced decoder pass: tokens [B,S_dec] -> logits."""
    s = tokens.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)
    enc_pos = jnp.arange(enc_out.shape[1], dtype=jnp.int32)
    x = embed(cfg, params["embed"], tokens)

    def body(h, lp):
        h = constrain_residual(h, cfg.residual_shard)
        if cfg.zero3_gather:
            lp = gather_weights(lp)
        h = h + attention(cfg, lp["self_attn"], apply_norm(cfg, h, lp["ln1"]),
                          positions, causal=True)
        k, v = cross_kv(cfg, lp["cross_attn"], enc_out)
        h = h + attention(cfg, lp["cross_attn"], apply_norm(cfg, h, lp["ln_x"]),
                          positions, causal=False, kv_override=(k, v, enc_pos))
        h = h + mlp(cfg, lp["mlp"], apply_norm(cfg, h, lp["ln2"]))
        return h, None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_fn, x, params["dec_layers"])
    x = apply_norm(cfg, x, params["final_norm"])
    return constrain_logits(unembed(cfg, params.get("unembed"), params["embed"], x))


def forward_encdec(cfg: ArchConfig, params, batch):
    enc_out = encode(cfg, params, batch["enc_embeds"])
    return decode_train(cfg, params, batch["tokens"], enc_out)


def init_cache_encdec(cfg: ArchConfig, batch: int, seq_len: int,
                      enc_len: int = None):
    window = seq_len if cfg.decode_window is None else min(cfg.decode_window, seq_len)
    spec = CacheSpec(batch=batch, window=window, n_kv_heads=cfg.n_kv_heads,
                     head_dim=cfg.head_dim, dtype=cfg.activation_dtype)
    self_cache = init_kv_cache(spec, cfg.n_layers)
    enc_len = enc_len or max(seq_len // cfg.enc_seq_divisor, 1)
    z = lambda: jnp.zeros((cfg.n_layers, batch, enc_len, cfg.n_kv_heads,
                           cfg.head_dim), jnp.dtype(cfg.activation_dtype))
    return {
        "k": self_cache["k"], "v": self_cache["v"],
        "slot_pos": self_cache["slot_pos"],
        "cross_k": z(), "cross_v": z(),
        "length": jnp.zeros((), jnp.int32),
    }


def prefill_cross_cache(cfg: ArchConfig, params, cache, enc_embeds):
    """Run the encoder once and fill the per-layer cross K/V caches."""
    enc_out = encode(cfg, params, enc_embeds)

    def per_layer(lp):
        k, v = cross_kv(cfg, lp["cross_attn"], enc_out)
        return k, v

    ks, vs = jax.vmap(per_layer)(params["dec_layers"])
    return dict(cache, cross_k=ks.astype(cache["cross_k"].dtype),
                cross_v=vs.astype(cache["cross_v"].dtype))


def decode_step_encdec(cfg: ArchConfig, params, cache, tokens):
    """One decoder token against self ring-cache + cross caches."""
    x = embed(cfg, params["embed"], tokens)
    length = cache["length"]
    enc_len = cache["cross_k"].shape[2]
    enc_pos = jnp.arange(enc_len, dtype=jnp.int32)

    def body(h, inp):
        lp, lc_k, lc_v, lc_sp, ck, cv = inp
        lc = {"k": lc_k, "v": lc_v, "slot_pos": lc_sp}
        a, lc_new = decode_attention(cfg, lp["self_attn"],
                                     apply_norm(cfg, h, lp["ln1"]), lc, length)
        h = h + a
        h = h + attention(cfg, lp["cross_attn"], apply_norm(cfg, h, lp["ln_x"]),
                          length[None].astype(jnp.int32), causal=False,
                          kv_override=(ck, cv, enc_pos))
        h = h + mlp(cfg, lp["mlp"], apply_norm(cfg, h, lp["ln2"]))
        return h, (lc_new["k"], lc_new["v"], lc_new["slot_pos"])

    x, (nk, nv, nsp) = jax.lax.scan(
        body, x,
        (params["dec_layers"], cache["k"], cache["v"], cache["slot_pos"],
         cache["cross_k"], cache["cross_v"]))
    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params.get("unembed"), params["embed"], x)
    new_cache = dict(cache, k=nk, v=nv, slot_pos=nsp, length=length + 1)
    return logits, new_cache
