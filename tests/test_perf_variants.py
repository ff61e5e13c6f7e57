"""The sharding-lever config variants must preserve model semantics (CPU,
no mesh)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.lm import build_lm
from repro.models.lm.moe import init_moe_ffn, moe_ffn


def test_moe_batch_local_matches_global():
    """With generous capacity the two dispatch strategies agree exactly."""
    cfg = get_config("mixtral-8x7b").reduced(capacity_factor=8.0)
    p = init_moe_ffn(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (2, 8, cfg.d_model)) * 0.3, jnp.float32)
    o1, a1 = moe_ffn(cfg, p, x)
    o2, a2 = moe_ffn(dataclasses.replace(cfg, moe_dispatch="batch_local"), p, x)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
    np.testing.assert_allclose(float(a1), float(a2), rtol=1e-4)


@pytest.mark.parametrize("overrides", [
    {"residual_shard": "batch_seq"},
    {"zero3_gather": True},
    {"attn_probs_bf16": True},
    {"moe_dispatch": "batch_local"},
])
def test_variant_forward_close_to_baseline(overrides):
    """Off-mesh, every sharding lever is numerically (near-)neutral."""
    arch = "mixtral-8x7b" if "moe_dispatch" in overrides else "deepseek-7b"
    cfg = get_config(arch).reduced()
    if "moe_dispatch" in overrides:
        # the dispatch strategies agree exactly only when no tokens are
        # dropped (they drop DIFFERENT overflow tokens at tight capacity)
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    lm = build_lm(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 32)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (2, 32)), jnp.int32),
    }
    base, _ = lm.forward(params, batch)
    lm2 = build_lm(dataclasses.replace(cfg, **overrides))
    var, _ = lm2.forward(params, batch)
    tol = 5e-2 if overrides.get("attn_probs_bf16") else 2e-3
    np.testing.assert_allclose(np.asarray(base, np.float32),
                               np.asarray(var, np.float32), atol=tol, rtol=tol)


def test_gossip_bf16_close_to_fp32():
    from repro.dist.dfl_step import decdiff_gossip
    from repro.utils.pytree import tree_l2_dist, tree_random_like, tree_stack

    proto = {"w": jnp.zeros((32, 16))}
    models = [tree_random_like(jax.random.PRNGKey(i), proto) for i in range(3)]
    st = tree_stack(models)
    adj = jnp.asarray([[0, .5, .5], [.5, 0, .5], [.5, .5, 0]], jnp.float32)
    full = decdiff_gossip(st, adj)
    half = decdiff_gossip(st, adj, gossip_dtype=jnp.bfloat16)
    assert float(tree_l2_dist(full, half)) < 0.05
