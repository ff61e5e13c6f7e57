"""Mamba2 (SSD — state-space duality) LM, pure JAX, chunk-parallel.

Implements the SSD block decomposition of arXiv:2405.21060: the sequence is
split into chunks of Q tokens; within a chunk the output is the quadratic
"attention-like" term (C_i·B_j masked by the decay kernel), across chunks an
O(1)-per-chunk recurrent state is carried by `lax.scan`.  Total work is
O(S·Q) instead of O(S^2), and decode keeps a per-head [P, N] recurrent state
(natively sub-quadratic: `long_500k` runs without any attention window).

All decay factors are exp of non-positive numbers (a = -exp(A_log)·dt < 0),
so every exponential in the chunked path is <= 1 — numerically safe in bf16.

Layer structure (mamba2):
  in_proj -> (z | xBC | dt); causal depthwise conv on xBC; SSD core;
  gated RMSNorm (y * silu(z)); out_proj.
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
    _dense_init,
    apply_norm,
    embed,
    init_embedding,
    init_linear,
    init_norm,
    unembed,
)


def _split_dims(cfg: ArchConfig):
    di = cfg.ssm_inner
    gn = cfg.ssm_groups * cfg.ssm_state
    h = cfg.ssm_heads
    return di, gn, h


def init_ssm_layer(rng, cfg: ArchConfig):
    di, gn, h = _split_dims(cfg)
    d = cfg.d_model
    conv_ch = di + 2 * gn
    k1, k2, k3 = jax.random.split(rng, 3)
    # dt bias init so softplus(dt_bias) spans ~[1e-3, 1e-1] (mamba2 default)
    u = jax.random.uniform(k3, (h,), jnp.float32)
    dt0 = jnp.exp(u * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))  # inverse softplus
    return {
        "norm": init_norm(cfg),
        "in_proj": init_linear(k1, d, 2 * di + 2 * gn + h, cfg),
        "conv_w": (_dense_init(k2, (cfg.ssm_conv, conv_ch), cfg.ssm_conv, cfg.pdtype)),
        "conv_b": jnp.zeros((conv_ch,), cfg.pdtype),
        "A_log": jnp.zeros((h,), jnp.float32),  # A = -exp(0) = -1
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "gate_norm": {"scale": jnp.ones((di,), cfg.pdtype)},
        "out_proj": init_linear(jax.random.fold_in(rng, 7), di, d, cfg),
    }


def _causal_depthwise_conv(x, w, b):
    """x [B,S,C], w [K,C] depthwise causal conv."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = jax.lax.conv_general_dilated(
        xp, w[:, None, :].astype(x.dtype),  # [K,1,C] HIO-ish
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1],
    )
    return y + b.astype(y.dtype)


def _project(cfg: ArchConfig, lp, x):
    """Shared pre-SSD projection: returns (z, xBC_conv_in, dt_raw)."""
    di, gn, h = _split_dims(cfg)
    zxbcdt = jnp.einsum("...d,df->...f", x, lp["in_proj"]["w"].astype(x.dtype))
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di : 2 * di + 2 * gn]
    dt_raw = zxbcdt[..., 2 * di + 2 * gn :]
    return z, xbc, dt_raw


def _split_xbc(cfg: ArchConfig, xbc):
    di, gn, _ = _split_dims(cfg)
    n, g = cfg.ssm_state, cfg.ssm_groups
    x_ssm = xbc[..., :di]
    b_mat = xbc[..., di : di + gn]
    c_mat = xbc[..., di + gn :]
    shape = xbc.shape[:-1]
    return (x_ssm.reshape(*shape, cfg.ssm_heads, cfg.ssm_head_dim),
            b_mat.reshape(*shape, g, n),
            c_mat.reshape(*shape, g, n))


def _expand_groups(cfg: ArchConfig, m):
    """[..., G, N] -> [..., H, N] by repeating each group for its heads."""
    reps = cfg.ssm_heads // cfg.ssm_groups
    return jnp.repeat(m, reps, axis=-2)


def ssd_chunked(cfg: ArchConfig, x, b_mat, c_mat, a, state0=None):
    """SSD core. x [B,S,H,P]; b/c [B,S,H,N]; a [B,S,H] (negative).

    Returns (y [B,S,H,P], final_state [B,H,P,N])."""
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    q = min(cfg.ssm_chunk, s)
    assert s % q == 0, (s, q)
    nc = s // q

    def resh(t):
        return t.reshape(bsz, nc, q, *t.shape[2:]).transpose(1, 0, *range(2, t.ndim + 1))

    xc, bc_, cc_, ac = map(resh, (x, b_mat, c_mat, a))  # leading nc

    if state0 is None:
        state0 = jnp.zeros((bsz, h, p, n), jnp.float32)

    causal = jnp.tril(jnp.ones((q, q), bool))

    def chunk_body(state, inp):
        xk, bk, ck, ak = inp  # [B,q,H,P], [B,q,H,N], ..., [B,q,H]
        xk32 = xk.astype(jnp.float32)
        bk32 = bk.astype(jnp.float32)
        ck32 = ck.astype(jnp.float32)
        ca = jnp.cumsum(ak, axis=1)  # [B,q,H], non-increasing
        total = ca[:, -1]  # [B,H]
        # intra-chunk quadratic term
        cb = jnp.einsum("bihn,bjhn->bhij", ck32, bk32)
        decay = jnp.exp(ca[:, :, None, :] - ca[:, None, :, :])  # [B,i,j,H]
        decay = jnp.where(causal[None, :, :, None], decay, 0.0)
        kern = cb * decay.transpose(0, 3, 1, 2)  # [B,H,i,j]
        y_intra = jnp.einsum("bhij,bjhp->bihp", kern, xk32)
        # inter-chunk: contribution of carried state
        y_inter = jnp.einsum("bihn,bhpn->bihp", ck32 * jnp.exp(ca)[..., None], state)
        # state update
        w_j = jnp.exp(total[:, None] - ca)  # [B,q,H] decay to chunk end
        s_add = jnp.einsum("bjhp,bjhn->bhpn", xk32 * w_j[..., None], bk32)
        state_new = state * jnp.exp(total)[:, :, None, None] + s_add
        return state_new, (y_intra + y_inter).astype(x.dtype)

    state, ys = jax.lax.scan(chunk_body, state0, (xc, bc_, cc_, ac))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(bsz, s, h, p)
    return y, state


def ssm_block(cfg: ArchConfig, lp, x):
    """One mamba2 layer on x [B,S,D] (pre-norm residual block)."""
    h_in = apply_norm(cfg, x, lp["norm"])
    z, xbc, dt_raw = _project(cfg, lp, h_in)
    xbc = jax.nn.silu(_causal_depthwise_conv(xbc, lp["conv_w"], lp["conv_b"]))
    x_ssm, b_mat, c_mat = _split_xbc(cfg, xbc)
    b_h = _expand_groups(cfg, b_mat)
    c_h = _expand_groups(cfg, c_mat)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + lp["dt_bias"])  # [B,S,H]
    a = -jnp.exp(lp["A_log"]) * dt  # negative
    xdt = x_ssm.astype(jnp.float32) * dt[..., None]
    y, _ = ssd_chunked(cfg, xdt.astype(x.dtype), b_h, c_h, a)
    y = y.astype(jnp.float32) + lp["D"][None, None, :, None] * x_ssm.astype(jnp.float32)
    bsz, s = x.shape[:2]
    y = y.reshape(bsz, s, cfg.ssm_inner).astype(x.dtype)
    y = y * jax.nn.silu(z)
    from repro.models.lm.layers import rms_norm

    y = rms_norm(y, lp["gate_norm"]["scale"])
    out = jnp.einsum("...f,fd->...d", y, lp["out_proj"]["w"].astype(y.dtype))
    return x + out


def init_ssm_lm(rng, cfg: ArchConfig):
    k_emb, k_layers, k_unemb = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    return {
        "embed": init_embedding(k_emb, cfg),
        "layers": jax.vmap(lambda k: init_ssm_layer(k, cfg))(layer_keys),
        "final_norm": init_norm(cfg),
        "unembed": init_linear(k_unemb, cfg.d_model, cfg.vocab, cfg),
    }


def forward_ssm(cfg: ArchConfig, params, tokens, positions=None):
    del positions
    x = constrain_batch(embed(cfg, params["embed"], tokens))

    def body(h, lp):
        h = constrain_residual(h, cfg.residual_shard)
        if cfg.zero3_gather:
            lp = gather_weights(lp)
        return ssm_block(cfg, lp, h), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_fn, x, params["layers"])
    x = apply_norm(cfg, x, params["final_norm"])
    return constrain_logits(unembed(cfg, params.get("unembed"), params["embed"], x))


def init_cache_ssm(cfg: ArchConfig, batch: int, seq_len: int):
    """Recurrent decode state: O(1) in seq_len (the cache size does not
    depend on context length — that's the SSM selling point)."""
    del seq_len
    di, gn, h = _split_dims(cfg)
    conv_ch = di + 2 * gn
    return {
        "conv": jnp.zeros((cfg.n_layers, batch, cfg.ssm_conv - 1, conv_ch), cfg.adtype),
        "state": jnp.zeros((cfg.n_layers, batch, h, cfg.ssm_head_dim, cfg.ssm_state),
                           jnp.float32),
        "length": jnp.zeros((), jnp.int32),
    }


def ssm_decode_block(cfg: ArchConfig, lp, x1, conv_state, state):
    """Single-token recurrent step.  x1 [B,D]."""
    h_in = apply_norm(cfg, x1, lp["norm"])
    z, xbc, dt_raw = _project(cfg, lp, h_in)
    window = jnp.concatenate([conv_state, xbc[:, None, :]], axis=1)  # [B,K,C]
    conv_out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                          lp["conv_w"].astype(jnp.float32)) + lp["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(conv_out).astype(x1.dtype)
    new_conv_state = window[:, 1:]
    x_ssm, b_mat, c_mat = _split_xbc(cfg, xbc)
    b_h = _expand_groups(cfg, b_mat).astype(jnp.float32)  # [B,H,N]
    c_h = _expand_groups(cfg, c_mat).astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + lp["dt_bias"])  # [B,H]
    a = -jnp.exp(lp["A_log"]) * dt
    xdt = x_ssm.astype(jnp.float32) * dt[..., None]  # [B,H,P]
    state = state * jnp.exp(a)[:, :, None, None] + jnp.einsum("bhp,bhn->bhpn", xdt, b_h)
    y = jnp.einsum("bhn,bhpn->bhp", c_h, state)
    y = y + lp["D"][None, :, None] * x_ssm.astype(jnp.float32)
    y = y.reshape(x1.shape[0], cfg.ssm_inner).astype(x1.dtype) * jax.nn.silu(z)
    from repro.models.lm.layers import rms_norm

    y = rms_norm(y, lp["gate_norm"]["scale"])
    out = jnp.einsum("bf,fd->bd", y, lp["out_proj"]["w"].astype(y.dtype))
    return x1 + out, new_conv_state, state


def decode_step_ssm(cfg: ArchConfig, params, cache, tokens):
    x = embed(cfg, params["embed"], tokens)[:, 0]  # [B,D]

    def body(h, inp):
        lp, conv_c, st = inp
        h, conv_new, st_new = ssm_decode_block(cfg, lp, h, conv_c, st)
        return h, (conv_new, st_new)

    x, (conv_new, state_new) = jax.lax.scan(
        body, x, (params["layers"], cache["conv"], cache["state"]))
    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params.get("unembed"), params["embed"], x[:, None, :])
    return logits, {"conv": conv_new, "state": state_new, "length": cache["length"] + 1}
