"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    decdiff_update,
    decdiff_update_tree,
    neighbor_avg,
    vt_kl_loss_fused,
)
from repro.kernels.ref import (
    decdiff_update_ref,
    neighbor_avg_ref,
    vt_kl_grad_ref,
    vt_kl_loss_ref,
)
from repro.utils.pytree import tree_l2_dist, tree_random_like


@pytest.mark.parametrize("n", [17, 1000, 32768, 100_001, 500_000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decdiff_update_sweep(n, dtype):
    rng = np.random.default_rng(n)
    w = jnp.asarray(rng.standard_normal(n), dtype)
    wb = jnp.asarray(rng.standard_normal(n), dtype)
    got = decdiff_update(w, wb, s=1.0)
    want = decdiff_update_ref(w, wb, s=1.0)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("s", [1.0, 2.5])
def test_decdiff_update_s_param(s):
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal(5000), jnp.float32)
    wb = jnp.asarray(rng.standard_normal(5000), jnp.float32)
    np.testing.assert_allclose(decdiff_update(w, wb, s=s),
                               decdiff_update_ref(w, wb, s=s), rtol=1e-5)


def test_decdiff_update_tree_matches_core():
    from repro.core.decdiff import decdiff_step

    proto = {"a": jnp.zeros((64, 33)), "b": {"w": jnp.zeros((1000,))}}
    w = tree_random_like(jax.random.PRNGKey(0), proto)
    wb = tree_random_like(jax.random.PRNGKey(1), proto)
    got = decdiff_update_tree(w, wb)
    want = decdiff_step(w, wb)
    assert tree_l2_dist(got, want) < 1e-4


@pytest.mark.parametrize("b,v", [(1, 7), (4, 10), (64, 1000), (130, 4097),
                                 (8, 32000), (2, 151936)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_vt_kl_loss_sweep(b, v, dtype):
    rng = np.random.default_rng(b * v)
    z = jnp.asarray(rng.standard_normal((b, v)) * 3, dtype)
    y = jnp.asarray(rng.integers(0, v, b), jnp.int32)
    got = vt_kl_loss_fused(z, y, 0.95)
    want = vt_kl_loss_ref(z, y, 0.95)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(float(got), float(want), rtol=tol, atol=tol)


@pytest.mark.parametrize("b,v", [(4, 10), (64, 1000), (6, 4097)])
def test_vt_kl_grad_sweep(b, v):
    rng = np.random.default_rng(b + v)
    z = jnp.asarray(rng.standard_normal((b, v)) * 2, jnp.float32)
    y = jnp.asarray(rng.integers(0, v, b), jnp.int32)
    got = jax.grad(lambda zz: vt_kl_loss_fused(zz, y, 0.95))(z)
    want = vt_kl_grad_ref(z, y, 0.95)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("beta", [0.9, 0.95, 0.999])
def test_vt_kl_beta_sweep(beta):
    rng = np.random.default_rng(5)
    z = jnp.asarray(rng.standard_normal((32, 257)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 257, 32), jnp.int32)
    np.testing.assert_allclose(float(vt_kl_loss_fused(z, y, beta)),
                               float(vt_kl_loss_ref(z, y, beta)),
                               rtol=1e-4, atol=1e-5)


def test_vt_kernel_matches_core_closed_form():
    from repro.core.virtual_teacher import vt_kl_loss

    rng = np.random.default_rng(6)
    z = jnp.asarray(rng.standard_normal((16, 100)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 100, 16), jnp.int32)
    np.testing.assert_allclose(float(vt_kl_loss_fused(z, y, 0.95)),
                               float(vt_kl_loss(z, y, beta=0.95)), rtol=1e-4)


@pytest.mark.parametrize("n,d", [(1, 10), (3, 100), (16, 5000), (50, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_neighbor_avg_sweep(n, d, dtype):
    rng = np.random.default_rng(n * d)
    st = jnp.asarray(rng.standard_normal((n, d)), dtype)
    w = jnp.asarray(rng.random(n) + 0.1, jnp.float32)
    got = neighbor_avg(st, w)
    want = neighbor_avg_ref(st, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("n,d", [(1, 10), (3, 100), (16, 5000), (50, 2048)])
def test_dequant_neighbor_avg_sweep(n, d):
    from repro.kernels import dequant_neighbor_avg
    from repro.kernels.ref import dequant_neighbor_avg_ref

    rng = np.random.default_rng(n * d + 1)
    q = jnp.asarray(rng.integers(-127, 128, (n, d)), jnp.int8)
    sc = jnp.asarray(rng.random(n) * 0.02 + 1e-4, jnp.float32)
    w = jnp.asarray(rng.random(n) + 0.1, jnp.float32)
    got = dequant_neighbor_avg(q, sc, w)
    want = dequant_neighbor_avg_ref(q, sc, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_dequant_neighbor_avg_fuses_codec_payload():
    """Feeding the kernel a real int8 codec payload equals dequantize-then-
    neighbor_avg (the unfused two-pass reference)."""
    from repro.comm import make_codec
    from repro.kernels import dequant_neighbor_avg

    codec = make_codec("int8", stochastic=False)
    rng = np.random.default_rng(9)
    vecs = jnp.asarray(rng.standard_normal((6, 4096)), jnp.float32)
    enc = jax.vmap(lambda v: codec.encode(v)[0])(vecs)
    dq = jax.vmap(codec.decode)(enc)  # [6, 4096] dequantized models
    w = jnp.asarray(rng.random(6) + 0.1, jnp.float32)
    got = dequant_neighbor_avg(enc["q"], enc["scale"], w)
    want = neighbor_avg(dq, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("r,n,d", [(1, 1, 10), (2, 4, 100), (4, 8, 5000),
                                   (3, 50, 2048)])
def test_dequant_neighbor_avg_rows_sweep(r, n, d):
    """The receiver-block variant (the shard_map round's payload path)
    against its jnp oracle, including all-zero weight rows (the 'heard from
    nobody' case must yield a zero average, not NaN)."""
    from repro.kernels import dequant_neighbor_avg_rows
    from repro.kernels.ref import dequant_neighbor_avg_rows_ref

    rng = np.random.default_rng(r * 1000 + n * d)
    q = jnp.asarray(rng.integers(-127, 128, (n, d)), jnp.int8)
    sc = jnp.asarray(rng.random(n) * 0.02 + 1e-4, jnp.float32)
    wn = rng.random((r, n)).astype(np.float32)
    wn[0, :] = 0.0  # a fully-masked receiver row
    wn = jnp.asarray(wn)
    got = dequant_neighbor_avg_rows(q, sc, wn)
    want = dequant_neighbor_avg_rows_ref(q, sc, wn)
    assert got.shape == (r, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_array_equal(np.asarray(got)[0], np.zeros(d))


def test_dequant_neighbor_avg_rows_matches_single_receiver_kernel():
    """One row of the block kernel == the single-receiver kernel (modulo
    the latter's internal weight normalization)."""
    from repro.kernels import dequant_neighbor_avg, dequant_neighbor_avg_rows

    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.integers(-127, 128, (5, 4096)), jnp.int8)
    sc = jnp.asarray(rng.random(5) * 0.01 + 1e-4, jnp.float32)
    w = jnp.asarray(rng.random(5) + 0.1, jnp.float32)
    wn = (w / jnp.sum(w))[None, :]  # pre-normalized single row
    got = dequant_neighbor_avg_rows(q, sc, wn)[0]
    want = dequant_neighbor_avg(q, sc, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("b,w,kk,g,hd", [(1, 16, 1, 1, 16), (2, 600, 2, 2, 64),
                                         (4, 1024, 8, 1, 128), (3, 512, 4, 8, 64)])
@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, w, kk, g, hd, cache_dtype):
    from repro.kernels import decode_attention_fused
    from repro.kernels.ref import decode_attention_ref

    rng = np.random.default_rng(b * w + hd)
    h = kk * g
    q = jnp.asarray(rng.standard_normal((b, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, w, kk, hd)), cache_dtype)
    v = jnp.asarray(rng.standard_normal((b, w, kk, hd)), cache_dtype)
    filled = max(w - 5, 1)
    sp = jnp.asarray([i if i < filled else -1 for i in range(w)], jnp.int32)
    pos = jnp.int32(filled - 1)
    got = decode_attention_fused(q, k, v, sp, pos)
    want = decode_attention_ref(q, k, v, sp, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_ref_matches_model_layer():
    """The kernel-ref math equals the model's decode_attention output."""
    from repro.kernels.ref import decode_attention_ref
    from repro.models.lm.config import ArchConfig
    from repro.models.lm.layers import decode_attention, init_attention

    cfg = ArchConfig(arch_id="t", family="dense", d_model=64, n_heads=4,
                     n_kv_heads=2, head_dim=16, vocab=32,
                     param_dtype="float32", activation_dtype="float32")
    p = init_attention(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    b, w = 2, 8
    x = jnp.asarray(rng.standard_normal((b, 1, 64)) * 0.3, jnp.float32)
    lc = {
        "k": jnp.asarray(rng.standard_normal((b, w, 2, 16)), jnp.float32),
        "v": jnp.asarray(rng.standard_normal((b, w, 2, 16)), jnp.float32),
        "slot_pos": jnp.asarray([0, 1, 2, 3, -1, -1, -1, -1], jnp.int32),
    }
    length = jnp.int32(4)
    out_model, lc_new = decode_attention(cfg, p, x, lc, length)
    # reproduce via ref: project q the same way, use the UPDATED cache
    from repro.models.lm.layers import _project_qkv

    q, _, _ = _project_qkv(cfg, p, x, length[None], True)
    ref = decode_attention_ref(q[:, 0], lc_new["k"], lc_new["v"],
                               lc_new["slot_pos"], length)
    # model applies wo afterwards; compare pre-wo by inverting is overkill —
    # instead apply wo to ref and compare
    from repro.models.lm.layers import linear

    ref_out = linear(ref.reshape(b, 1, cfg.q_dim).astype(x.dtype), p["wo"])
    np.testing.assert_allclose(np.asarray(out_model), np.asarray(ref_out),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# exchange receiver-row gather (reverse-slot path of the per-edge transport)
# ---------------------------------------------------------------------------

def test_gather_rows_reverse_slot_roundtrip():
    """The per-edge transport's reverse-slot row gather out of the flattened
    [N*E, D] table reproduces the dense _swap_layout on a symmetric
    neighbour layout, bit for bit."""
    from repro.comm import CommConfig
    from repro.comm.transport import EdgeGossipTransport
    from repro.graphs.topology import make_topology

    topo = make_topology("ring", n=6)
    d = 10
    params = {"w": jnp.zeros((topo.num_nodes, d), jnp.float32)}
    tr = EdgeGossipTransport(CommConfig(codec="int8"), params,
                             topo.neighbor_idx, topo.neighbor_mask)
    n, e = tr.n, tr.e
    rng = np.random.default_rng(3)
    tbl = jnp.asarray(rng.standard_normal((n, e, d)), jnp.float32)
    out = tr._gather_receiver_rows(tbl, lambda a: a)["w"]
    ref = tbl[tr.nbr_idx, tr.rev_slot]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# interpret default — compiled on the TPU, interpreted on the CPU, else refused
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", None)])
def test_interpret_default_by_backend(monkeypatch, backend, interpret):
    from repro.kernels import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops._interpret_default()
    else:
        assert ops._interpret_default() is interpret
