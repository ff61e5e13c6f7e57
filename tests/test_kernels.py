"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import vt_kl_loss_fused
from repro.kernels.ref import vt_kl_grad_ref, vt_kl_loss_ref


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


def test_dequant_neighbor_avg_fuses_codec_payload():
    """Feeding the block kernel a real int8 codec payload, one receiver
    row, equals the codec's own decode followed by the weighted average
    (the unfused two-pass reference)."""
    from repro.comm import make_codec
    from repro.kernels import dequant_neighbor_avg_rows

    codec = make_codec("int8", stochastic=False)
    rng = np.random.default_rng(9)
    vecs = jnp.asarray(rng.standard_normal((6, 4096)), jnp.float32)
    enc = jax.vmap(lambda v: codec.encode(v)[0])(vecs)
    dq = jax.vmap(codec.decode)(enc)  # [6, 4096] dequantized models
    w = jnp.asarray(rng.random(6) + 0.1, jnp.float32)
    wn = (w / jnp.sum(w))[None, :]  # one pre-normalized receiver row
    got = dequant_neighbor_avg_rows(enc["q"], enc["scale"], wn)[0]
    want = jnp.einsum("n,nd->d", wn[0], dq)
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
