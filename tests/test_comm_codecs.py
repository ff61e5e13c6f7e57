"""Property tests for the comm codecs (repro.comm.codecs) and the trigger.

Three contracts, each checked two ways: deterministic seeded sweeps that run
in tier-1, and hypothesis fuzz versions (marked `fuzz`) that run in the
dedicated CI lane (`pytest -m fuzz`) so tier-1 stays fast:

  1. decode∘encode error bounds — exact (fp32), one-ulp relative (bf16),
     one quantization grain (int8), exact-on-support (top-k);
  2. bytes_on_wire exactness — the reported count equals the byte length of
     the serialized payload AND the shape-only static prediction;
  3. the error-feedback invariant — residual' + decode(payload) equals the
     pre-compression input + residual, up to the quantization grain, so
     compression delays information but never destroys it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import drift_gate, edge_delivery, make_codec, payload_nbytes

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # container lane: tier-1 runs the seeded sweeps only
    HAVE_HYPOTHESIS = False

ALL_CODECS = ("fp32", "bf16", "int8", "topk")


def _codec(name):
    # deterministic int8 so the seeded sweeps are reproducible; the
    # stochastic mode gets its own unbiasedness test below.
    return make_codec(name, **({"stochastic": False} if name == "int8" else {}))


def _vec(seed, n, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(n) * scale, jnp.float32)


def serialized_nbytes(payload) -> int:
    """Ground truth: actually serialize every leaf to raw bytes."""
    return sum(len(np.asarray(x).tobytes()) for x in jax.tree.leaves(payload))


# ---------------------------------------------------------------- contracts


def check_bytes_exact(codec, v):
    res = codec.init_residual(v)
    payload, _ = codec.encode(v, rng=jax.random.PRNGKey(0), residual=res)
    got = codec.bytes_on_wire(payload)
    assert got == serialized_nbytes(payload)
    assert got == codec.payload_bytes_for(int(v.shape[0]))
    assert got == payload_nbytes(payload)


def check_roundtrip_bound(name, codec, v):
    n = int(v.shape[0])
    payload, _ = codec.encode(v, residual=None)
    d = np.asarray(codec.decode(payload, out_size=n), np.float32)
    x = np.asarray(v, np.float32)
    if name == "fp32":
        assert np.array_equal(d, x)
    elif name == "bf16":
        # one bf16 ulp relative; atol floor for the subnormal range
        np.testing.assert_allclose(d, x, rtol=1.0 / 128, atol=1e-37)
    elif name == "int8":
        amax = np.max(np.abs(x))
        grain = (amax / 127.0) if amax > 0 else 1.0
        assert np.max(np.abs(d - x)) <= grain * (1 + 1e-5)
    elif name == "topk":
        # decoded entries are exact copies of the input on their support
        nz = d != 0
        assert np.array_equal(d[nz], x[nz])
        assert np.count_nonzero(nz) <= codec.k_for(n)


def check_ef_invariant(name, codec, v, res):
    if not codec.has_residual:
        return
    payload, res2 = codec.encode(v, residual=res)
    d = codec.decode(payload, out_size=int(v.shape[0]))
    x = np.asarray(v, np.float32) + np.asarray(res, np.float32)
    recon = np.asarray(res2, np.float32) + np.asarray(d, np.float32)
    if name == "topk":
        np.testing.assert_array_equal(recon, x)  # bitwise: scatter/gather
    else:
        amax = np.max(np.abs(x))
        grain = (amax / 127.0) if amax > 0 else 1.0
        assert np.max(np.abs(recon - x)) <= grain * 1e-4 + 1e-30


# ------------------------------------------------- tier-1 seeded sweeps


@pytest.mark.parametrize("name", ALL_CODECS)
@pytest.mark.parametrize("n,seed", [(17, 0), (1000, 1), (4096, 2)])
def test_bytes_on_wire_exact(name, n, seed):
    check_bytes_exact(_codec(name), _vec(seed, n))


@pytest.mark.parametrize("name", ALL_CODECS)
@pytest.mark.parametrize("n,seed,scale", [(17, 0, 1.0), (1000, 1, 100.0),
                                          (4096, 2, 1e-3)])
def test_roundtrip_error_bound(name, n, seed, scale):
    check_roundtrip_bound(name, _codec(name), _vec(seed, n, scale))


@pytest.mark.parametrize("name", ("int8", "topk"))
@pytest.mark.parametrize("n,seed", [(33, 3), (1000, 4)])
def test_error_feedback_invariant(name, n, seed):
    codec = _codec(name)
    v = _vec(seed, n)
    res = _vec(seed + 100, n, 0.3)
    check_ef_invariant(name, codec, v, res)


def test_compression_ratios():
    """The wire sizes behind the frontier: bf16 2x, int8 ~4x, top-k ~1/ratio."""
    d = 10_000
    fp32 = _codec("fp32").payload_bytes_for(d)
    assert fp32 == 4 * d
    assert _codec("bf16").payload_bytes_for(d) == 2 * d
    assert _codec("int8").payload_bytes_for(d) == d + 4  # + one fp32 scale
    topk = make_codec("topk", ratio=0.01)
    assert topk.payload_bytes_for(d) == 8 * topk.k_for(d) + 4  # idx+val, +len
    assert fp32 / _codec("int8").payload_bytes_for(d) > 3.9


def test_int8_stochastic_rounding_unbiased():
    """E[decode(encode(x))] == x for the stochastic quantizer."""
    codec = make_codec("int8", stochastic=True)
    v = _vec(7, 256)
    keys = jax.random.split(jax.random.PRNGKey(0), 512)

    def enc_dec(key):
        p, _ = codec.encode(v, rng=key)
        return codec.decode(p)

    mean = np.asarray(jnp.mean(jax.vmap(enc_dec)(keys), axis=0))
    grain = float(jnp.max(jnp.abs(v))) / 127.0
    # the mean must beat the deterministic worst case by a wide margin
    assert np.max(np.abs(mean - np.asarray(v))) < 0.2 * grain


def test_topk_picks_largest_magnitudes():
    codec = make_codec("topk", ratio=0.1)
    v = jnp.asarray(np.r_[np.zeros(90), np.arange(1, 11)], jnp.float32)
    payload, _ = codec.encode(v)
    assert sorted(np.asarray(payload["idx"]).tolist()) == list(range(90, 100))


def test_trigger_gate_semantics():
    w = jnp.asarray([[1.0, 0.0], [0.0, 0.0], [3.0, 4.0]], jnp.float32)
    last = jnp.zeros_like(w)
    gate0, drift = drift_gate(w, last, 0.0)
    assert np.array_equal(np.asarray(gate0), [1, 1, 1])  # 0 = always send
    np.testing.assert_allclose(np.asarray(drift), [1.0, 0.0, 5.0])
    gate2, _ = drift_gate(w, last, 2.0)
    assert np.array_equal(np.asarray(gate2), [0, 0, 1])
    # monotone: raising the threshold never turns a silent node into a sender
    gate9, _ = drift_gate(w, last, 9.0)
    assert np.all(np.asarray(gate9) <= np.asarray(gate2))


def test_edge_delivery_composes_gate_and_links():
    gate = jnp.asarray([1.0, 0.0, 1.0])
    nbr_idx = jnp.asarray([[1, 2], [0, 2], [0, 1]], jnp.int32)
    link = jnp.asarray([[1.0, 1.0], [1.0, 0.0], [0.0, 1.0]], jnp.float32)
    m = np.asarray(edge_delivery(gate, link, nbr_idx))
    # node0 hears: nbr1 (silent) -> 0, nbr2 (sent, link up) -> 1
    # node1 hears: nbr0 (sent) -> 1, nbr2 (sent, link DOWN) -> 0
    # node2 hears: nbr0 (sent, link DOWN) -> 0, nbr1 (silent) -> 0
    assert np.array_equal(m, [[0, 1], [1, 0], [0, 0]])


# --------------------------------------------------- hypothesis fuzz lane


if HAVE_HYPOTHESIS:
    big = float(np.float32(1e30))  # width=32 bounds must be float32 values
    finite = st.floats(min_value=-big, max_value=big,
                       allow_nan=False, allow_infinity=False, width=32)
    vectors = st.lists(finite, min_size=1, max_size=300).map(
        lambda xs: jnp.asarray(xs, jnp.float32))

    @pytest.mark.fuzz
    @pytest.mark.parametrize("name", ALL_CODECS)
    @settings(max_examples=50, deadline=None)
    @given(v=vectors)
    def test_fuzz_bytes_on_wire_exact(name, v):
        check_bytes_exact(_codec(name), v)

    @pytest.mark.fuzz
    @pytest.mark.parametrize("name", ALL_CODECS)
    @settings(max_examples=50, deadline=None)
    @given(v=vectors)
    def test_fuzz_roundtrip_error_bound(name, v):
        check_roundtrip_bound(name, _codec(name), v)

    @pytest.mark.fuzz
    @pytest.mark.parametrize("name", ("int8", "topk"))
    @settings(max_examples=50, deadline=None)
    @given(v=vectors, seed=st.integers(0, 2**31 - 1))
    def test_fuzz_error_feedback_invariant(name, v, seed):
        res = jnp.asarray(
            np.random.default_rng(seed).standard_normal(v.shape[0]) * 0.3,
            jnp.float32)
        check_ef_invariant(name, _codec(name), v, res)
