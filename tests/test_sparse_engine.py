"""The sparse node-axis engine vs its dense oracle.

The acceptance pins for `Experiment(layout="sparse")`:

  1. oracle — on ≤64-node BA/ER/star worlds the sparse edge-list engine is
     BIT-EQUAL to the dense padded engine: final params, total comm bytes,
     and the per-round trigger history, across methods × comm configs (at
     participation=1.0, where the two layouts consume identical rng);
  2. backends — the sparse layout lowers to shard_map bit-identically to
     vmap (single-pod here, the forced 4-device mesh in the multihost
     lane);
  3. kernels — `segment_neighbor_avg_rows` (the table form, which gathers
     neighbour rows inside the kernel) equals the panel form bit for bit
     and is bitwise invariant to row blocking, K zero-padding (any row or
     finite garbage under zero weight), and feature-column tiling: the
     properties the oracle equality rests on;
  4. plan — `build_sparse_plan` lays every node out exactly once, in the
     contiguous pod blocks shard_map slices, with the same ω·|D_src|
     weight product as the dense layout;
  5. errors — layout support is CAPABILITY-driven: the strategy's
     Capabilities record (plus the one derived restriction — a gossip
     strategy without a flat_aggregate form) decides what constructs, and
     the rejection message lists exactly which layouts support the method.
     The historical sparse carve-outs (dynamics, per-edge transport,
     CFA-GE) are lifted — their equivalence pins live in
     tests/test_sparse_parity.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import CommConfig
from repro.dynamics import EdgeDropout
from repro.engine import Experiment, Schedule, World
from repro.engine.neighborhood import (
    DenseNeighborhood,
    SparseNeighborhood,
    _bucket_width,
    build_sparse_plan,
)
from repro.graphs.sparse import (
    SparseTopology,
    sparse_barabasi_albert,
    sparse_erdos_renyi,
    sparse_ring,
    sparse_star,
)
from repro.kernels import segment_avg as _sa
from repro.kernels.ops import (
    dequant_segment_neighbor_avg,
    segment_neighbor_avg,
    segment_neighbor_avg_rows,
)


def _world(st: SparseTopology, seed: int = 0, dim: int = 16,
           per_node: int = 4, classes: int = 10) -> World:
    """A node-axis-sized world (tiny model, tiny shards) over `st`."""
    from repro.models.mlp_cnn import make_mlp

    rng = np.random.default_rng(seed)
    n = st.num_nodes
    xs = [rng.normal(size=(per_node, dim)).astype(np.float32)
          for _ in range(n)]
    ys = [rng.integers(0, classes, size=per_node).astype(np.int32)
          for _ in range(n)]
    return World(
        model=make_mlp(num_classes=classes, input_dim=dim, hidden=(16,)),
        topo=st, xs=xs, ys=ys,
        x_test=rng.normal(size=(32, dim)).astype(np.float32),
        y_test=rng.integers(0, classes, size=32).astype(np.int32))


TINY = dict(steps_per_round=1, batch_size=4, lr=0.1, eval_batch=32, seed=3)


def _run(world, method, layout, rounds=3, comm=None, backend="vmap", **kw):
    exp = Experiment(world, method, comm=comm, backend=backend,
                     layout=layout,
                     schedule=Schedule(rounds=rounds, eval_every=rounds,
                                       mode="loop"),
                     **{**TINY, **kw})
    exp.run()
    return exp


def _assert_experiments_bit_equal(a: Experiment, b: Experiment):
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert a.comm_bytes_total == b.comm_bytes_total
    assert a.trig_history == b.trig_history


# ------------------------------------------------------------------ oracle


@pytest.fixture(scope="module")
def ba_world():
    return _world(sparse_barabasi_albert(n=16, m=2, seed=0))


@pytest.mark.parametrize("method", ["decavg", "cfa", "decdiff+vt", "fedavg",
                                    "isol"])
def test_sparse_matches_dense_per_method(ba_world, method):
    dense = _run(ba_world, method, "dense")
    sparse = _run(ba_world, method, "sparse")
    _assert_experiments_bit_equal(dense, sparse)


@pytest.mark.parametrize("st", [
    sparse_erdos_renyi(n=24, p=0.25, seed=1),
    sparse_barabasi_albert(n=24, m=1, seed=2),  # hub-heavy tree
    sparse_star(17),                            # max_degree = N - 1
], ids=["er24", "ba24-m1", "star17"])
def test_sparse_matches_dense_per_graph(st):
    world = _world(st, seed=1)
    dense = _run(world, "decdiff", "dense")
    sparse = _run(world, "decdiff", "sparse")
    _assert_experiments_bit_equal(dense, sparse)


@pytest.mark.parametrize("comm", [
    CommConfig(codec="int8", trigger_threshold=0.0),
    CommConfig(codec="fp32", trigger_threshold=0.05, on_silence="stale"),
    CommConfig(codec="fp32", trigger_threshold=0.05, on_silence="drop"),
], ids=["int8", "fp32-trig-stale", "fp32-trig-drop"])
def test_sparse_matches_dense_with_transport(ba_world, comm):
    """Per-node transport over the sparse layout: params, BYTES, and the
    trigger history reproduce the dense engine bit-for-bit (the byte
    accounting multiplies fired gates into in-degrees, a quantity both
    layouts derive from their own edge structure)."""
    dense = _run(ba_world, "decdiff", "dense", comm=comm)
    sparse = _run(ba_world, "decdiff", "sparse", comm=comm)
    assert dense.comm_bytes_total > 0
    _assert_experiments_bit_equal(dense, sparse)


def test_sparse_participation_runs_and_stays_finite(ba_world):
    """participation < 1 draws per-[N,max_deg]-slot uniforms on the dense
    layout and per-directed-edge uniforms on the sparse one — the streams
    are documented as different, so this is a liveness pin, not an
    equality pin."""
    exp = _run(ba_world, "decdiff", "sparse", participation=0.5)
    for leaf in jax.tree.leaves(exp.params):
        assert np.isfinite(np.asarray(leaf)).all()


# ----------------------------------------------------------------- backends


def test_sparse_shardmap_single_pod_matches_vmap(ba_world):
    vm = _run(ba_world, "decdiff", "sparse", backend="vmap")
    sm = _run(ba_world, "decdiff", "sparse", backend="shard_map")
    _assert_experiments_bit_equal(vm, sm)


@pytest.mark.multihost
@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs >= 4 devices (forced-multihost CI lane)")
@pytest.mark.parametrize("comm", [None,
                                  CommConfig(codec="int8",
                                             trigger_threshold=0.05)],
                         ids=["plain", "int8-trig"])
def test_sparse_shardmap_four_pods_matches_vmap(ba_world, comm):
    """The real pod split: 4 pods × 4 nodes, each pod reducing its own
    degree buckets from the all_gathered table — bit-equal to vmap."""
    vm = _run(ba_world, "decdiff", "sparse", comm=comm, backend="vmap")
    sm = _run(ba_world, "decdiff", "sparse", comm=comm, backend="shard_map")
    _assert_experiments_bit_equal(vm, sm)


# ------------------------------------------------------------------ kernels


def _rows_ref(w, v):
    """The contract: each receiver row contracted by its OWN einsum."""
    return jnp.stack([
        jnp.einsum("k,kd->d", w[r], v[r],
                   preferred_element_type=jnp.float32)
        for r in range(w.shape[0])])


def _gather_inputs(rng, m, b, k, d, live=0.7):
    """A [m, d] table, [b, k] row ids (repeats allowed) and weights with a
    share of zero-weight slots."""
    table = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, m, size=(b, k)).astype(np.int32))
    w = jnp.asarray((rng.random((b, k)) < live).astype(np.float32)
                    * rng.uniform(0.5, 2.0, (b, k)).astype(np.float32))
    return table, idx, w


@pytest.mark.parametrize("cols", [None, 128])
def test_segment_avg_gather_bitwise_per_row(cols):
    """The gather kernel against the per-row einsum contract, in one
    full-width column tile and in 128-wide tiles with a partial last one
    (d = 300): column tiling cannot move a bit."""
    rng = np.random.default_rng(0)
    m, b, k, d = 12, _sa.ROWS * 2, 8, 300
    table, idx, w = _gather_inputs(rng, m, b, k, d)
    ip = jnp.where(w != 0, idx, -1)
    sums, tot = _sa.segment_avg_gather(ip, w, table[:, None, :],
                                       cols=cols or d)
    assert np.array_equal(np.asarray(sums),
                          np.asarray(_rows_ref(w, table[idx])))
    ones = jnp.ones((b, k, _sa.LANES), jnp.float32)
    assert np.array_equal(np.asarray(tot), np.asarray(_rows_ref(w, ones)))


@pytest.mark.parametrize("d", [1, 100, 128, 150, 1000, 4099])
@pytest.mark.parametrize("k", [1, 2, 8, 13, 16, 17, 33])
def test_segment_neighbor_avg_rows_shapes(k, d):
    """The engine's reduce over a grid of degree widths and model widths:
    K below, at and past a multiple of 8 (zero-padded without moving a
    bit), D below, at and past a lane multiple, bit-equal to the per-row
    contract everywhere."""
    rng = np.random.default_rng(10 + 7 * k + d)
    table, idx, w = _gather_inputs(rng, 20, 11, k, d)
    sums, tot = segment_neighbor_avg_rows(table, idx, w)
    assert np.array_equal(np.asarray(sums),
                          np.asarray(_rows_ref(w, table[idx])))
    assert np.array_equal(np.asarray(tot), np.asarray(_rows_ref(
        w, jnp.ones((11, k, 1), jnp.float32))[:, 0]))


@pytest.mark.parametrize("repeats", [False, True])
def test_segment_neighbor_avg_rows_equals_panel_form(repeats):
    """The table form equals the panel form over `table[idx]` bit for bit,
    also when receivers share rows or name one row in several slots."""
    rng = np.random.default_rng(11)
    table, idx, w = _gather_inputs(rng, 6 if repeats else 40, 21, 16, 97)
    if repeats:
        idx = idx.at[:, 1].set(idx[:, 0])
    sums, tot = segment_neighbor_avg_rows(table, idx, w)
    sums_p, tot_p = segment_neighbor_avg(table[idx], w)
    assert np.array_equal(np.asarray(sums), np.asarray(sums_p))
    assert np.array_equal(np.asarray(tot), np.asarray(tot_p))


@pytest.mark.parametrize("pad_row", [0, 7, 30, 31])
def test_segment_neighbor_avg_rows_zero_weight_slots_bit_neutral(pad_row):
    """Zero-weight slots are bit-neutral wherever their ids point: at any
    valid row (the dense layout's padding, an undelivered edge) or at rows
    of finite garbage (30, 31 hold ±3.4e38)."""
    rng = np.random.default_rng(12)
    m, b, k, d = 30, 9, 5, 64
    table, idx, w = _gather_inputs(rng, m, b, k, d, live=1.0)
    sums, tot = segment_neighbor_avg_rows(table, idx, w)
    garbage = jnp.concatenate([table, jnp.full((1, d), 3.4e38),
                               jnp.full((1, d), -3.4e38)])
    idx_pad = jnp.concatenate(
        [idx, jnp.full((b, 11), pad_row, jnp.int32)], axis=1)
    w_pad = jnp.concatenate([w, jnp.zeros((b, 11), jnp.float32)], axis=1)
    sums_p, tot_p = segment_neighbor_avg_rows(garbage, idx_pad, w_pad)
    assert np.array_equal(np.asarray(sums), np.asarray(sums_p))
    assert np.array_equal(np.asarray(tot), np.asarray(tot_p))


def test_dequant_segment_avg_chunk_bitwise_per_row():
    rng = np.random.default_rng(1)
    ws = jnp.asarray(rng.normal(size=(_sa.ROWS, 8)).astype(np.float32))
    q = jnp.asarray(rng.integers(-127, 128, size=(_sa.ROWS, 8, 256),
                                 dtype=np.int8))
    out = _sa.dequant_segment_avg_chunk(ws, q)
    ref = _rows_ref(ws, q.astype(jnp.float32))
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_segment_neighbor_avg_row_block_invariant():
    """sums[i] must not depend on which rows share the batch — the property
    that makes a pod's block reduce bit-equal to vmap's full-N reduce."""
    rng = np.random.default_rng(2)
    b, k, d = 21, 8, 100
    vals = jnp.asarray(rng.normal(size=(b, k, d)).astype(np.float32))
    w = jnp.asarray((rng.random((b, k)) < 0.7).astype(np.float32)
                    * rng.uniform(0.5, 2.0, (b, k)).astype(np.float32))
    sums, tot = segment_neighbor_avg(vals, w)
    for i in range(0, b, 5):
        s1, t1 = segment_neighbor_avg(vals[i:i + 1], w[i:i + 1])
        assert np.array_equal(np.asarray(sums[i]), np.asarray(s1[0]))
        assert np.array_equal(np.asarray(tot[i]), np.asarray(t1[0]))


def test_segment_neighbor_avg_k_pad_garbage_invariant():
    """Zero-weight slots with FINITE garbage values are bit-neutral: the
    dense max_deg padding and the sparse power-of-two bucket padding may
    hold anything."""
    rng = np.random.default_rng(3)
    b, k, d = 8, 5, 64
    vals = jnp.asarray(rng.normal(size=(b, k, d)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.5, 2.0, (b, k)).astype(np.float32))
    sums, tot = segment_neighbor_avg(vals, w)
    garbage = jnp.full((b, 11, d), 3.4e38, jnp.float32)
    vals_pad = jnp.concatenate([vals, garbage], axis=1)
    w_pad = jnp.concatenate([w, jnp.zeros((b, 11), jnp.float32)], axis=1)
    sums_p, tot_p = segment_neighbor_avg(vals_pad, w_pad)
    assert np.array_equal(np.asarray(sums), np.asarray(sums_p))
    assert np.array_equal(np.asarray(tot), np.asarray(tot_p))


def test_segment_neighbor_avg_totals_ride_the_contraction():
    rng = np.random.default_rng(4)
    b, k, d = 9, 6, 40
    vals = jnp.asarray(rng.normal(size=(b, k, d)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.0, 2.0, (b, k)).astype(np.float32))
    _, tot = segment_neighbor_avg(vals, w)
    assert np.allclose(np.asarray(tot), np.asarray(w).sum(axis=1), rtol=1e-6)


@pytest.mark.parametrize("d", [1, 128, 4099])
@pytest.mark.parametrize("k", [1, 16])
def test_dequant_segment_neighbor_avg_matches_reference(k, d):
    rng = np.random.default_rng(5 + k + d)
    b = 11
    q = jnp.asarray(rng.integers(-127, 128, size=(b, k, d), dtype=np.int8))
    scales = jnp.asarray(rng.uniform(0.01, 0.1, (b, k)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.0, 2.0, (b, k)).astype(np.float32))
    out = dequant_segment_neighbor_avg(q, scales, w)
    ref = _rows_ref(w * scales, q.astype(jnp.float32))
    assert np.array_equal(np.asarray(out), np.asarray(ref))


# --------------------------------------------------------------------- plan


def test_bucket_width_floor_and_pow2():
    assert [_bucket_width(d) for d in (0, 1, 7, 8, 9, 16, 17, 100)] == \
        [8, 8, 8, 8, 16, 16, 32, 128]


def test_sparse_plan_star_layout():
    """Star: the hub lands in a wide bucket, the leaves in the width-8
    floor bucket; weights carry ω_e·|D_src| exactly."""
    n = 16
    st = sparse_star(n)
    rng = np.random.default_rng(7)
    counts = rng.integers(1, 9, n).astype(np.int32)
    plan = build_sparse_plan(st, counts, n_pods=2)
    assert plan.per_pod == 8 and plan.n_pods == 2
    assert plan.num_directed == st.num_directed
    assert plan.widths == (8, 16)
    assert np.array_equal(np.asarray(plan.degrees),
                          st.degrees.astype(np.float32))
    # every node appears in exactly one bucket row of its own pod
    seen = np.zeros(n, np.int64)
    for wd in plan.widths:
        bk = plan.buckets[wd]
        p_, b_, k_ = bk.src.shape
        assert p_ == 2 and k_ == wd
        assert bk.wgt.shape == (p_, b_, k_) and bk.epos.shape == (p_, b_, k_)
        for p in range(2):
            for row in range(b_):
                rl = int(bk.rows_local[p, row])
                if rl == plan.per_pod:  # trash row: inert padding
                    assert np.asarray(bk.wgt[p, row]).sum() == 0
                    continue
                i = p * plan.per_pod + rl
                seen[i] += 1
                lo, hi = int(st.row_offsets[i]), int(st.row_offsets[i + 1])
                deg = hi - lo
                assert _bucket_width(deg) == wd
                assert np.array_equal(np.asarray(bk.src[p, row, :deg]),
                                      st.edge_src[lo:hi])
                assert np.array_equal(np.asarray(bk.epos[p, row, :deg]),
                                      np.arange(lo, hi))
                ref_w = (st.edge_weight[lo:hi]
                         * counts[st.edge_src[lo:hi]].astype(np.float32))
                assert np.array_equal(np.asarray(bk.wgt[p, row, :deg]), ref_w)
                assert (np.asarray(bk.wgt[p, row, deg:]) == 0).all()
    assert (seen == 1).all()


def test_sparse_plan_rejects_non_tiling_pods():
    st = sparse_star(17)
    with pytest.raises(ValueError, match="do not tile"):
        build_sparse_plan(st, np.ones(17, np.int32), n_pods=2)


def test_neighborhood_views_bit_equal():
    """DenseNeighborhood vs SparseNeighborhood on the same star graph and
    model table: reduce / reduce_delta / n_active all bit-equal — the unit
    form of the end-to-end oracle pins above."""
    n, d = 17, 23
    st = sparse_star(n)
    topo = st.to_topology()
    rng = np.random.default_rng(8)
    counts = rng.integers(1, 9, n).astype(np.int32)
    table = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    gate = jnp.asarray((rng.random(n) < 0.6).astype(np.float32))

    idx = np.maximum(topo.neighbor_idx.astype(np.int32), 0)
    w_dense = (topo.neighbor_weights()
               * counts[idx].astype(np.float32)
               * topo.neighbor_mask)
    w_dense = jnp.asarray(w_dense) * gate[jnp.asarray(idx)]
    dn = DenseNeighborhood(table, jnp.asarray(idx), w_dense, table,
                           unflatten_fn=lambda x: x)

    plan = build_sparse_plan(st, counts, n_pods=1)
    sn = SparseNeighborhood(plan, jnp.int32(0), table, table,
                            unflatten_fn=lambda x: x, gate_vec=gate,
                            link_u=None, participation=1.0)

    for a, b in zip(dn.reduce(), sn.reduce()):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(dn.reduce_delta(), sn.reduce_delta()):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(dn.n_active()),
                          np.asarray(sn.n_active()))


# ------------------------------------------------------------------- errors


def test_lifted_combinations_construct_on_sparse(ba_world):
    """The three historical sparse carve-outs — dynamics, per-edge
    transport, CFA-GE — all construct now (their bit-parity pins live in
    tests/test_sparse_parity.py)."""
    from repro.comm import SparseEdgeGossipTransport

    world = dataclasses.replace(ba_world, dynamics=EdgeDropout(p=0.2))
    exp = Experiment(world, "decdiff", layout="sparse",
                     schedule=Schedule(rounds=1, eval_every=1, mode="loop"),
                     **TINY)
    assert exp.bound_dyn is not None
    exp = Experiment(ba_world, "decdiff", layout="sparse",
                     comm=CommConfig(codec="int8", per_edge=True),
                     schedule=Schedule(rounds=1, eval_every=1, mode="loop"),
                     **TINY)
    assert isinstance(exp.transport, SparseEdgeGossipTransport)
    exp = Experiment(ba_world, "cfa-ge", layout="sparse",
                     schedule=Schedule(rounds=1, eval_every=1, mode="loop"),
                     **TINY)
    assert exp.strategy.capabilities.grad_exchange


def test_gossip_without_flat_form_is_dense_only(ba_world):
    """The derived layout restriction: a gossip strategy with no
    flat_aggregate form has only the padded-gather lowering, and the error
    names the surviving layouts."""
    from repro.engine.strategies import AggregationStrategy, register_method

    class _PaddedOnlyStrategy(AggregationStrategy):
        name = "padded-only"

        def aggregate(self, exp, state, params, gathered, mask):
            return params

    register_method("padded-only-test", _PaddedOnlyStrategy(),
                    overwrite=True)
    with pytest.raises(ValueError, match=r"flat_aggregate") as ei:
        Experiment(ba_world, "padded-only-test", layout="sparse")
    assert "('dense',)" in str(ei.value)
    # ...and the same strategy still constructs on the dense layout.
    Experiment(ba_world, "padded-only-test", layout="dense",
               schedule=Schedule(rounds=1, eval_every=1, mode="loop"),
               **TINY)


def test_declared_capability_layouts_drive_rejection(ba_world):
    """A strategy that declares layouts=('dense',) in its Capabilities
    record is rejected on sparse FROM the record — no string-matching on
    method names — and the message lists the supported layouts."""
    from repro.engine.strategies import (Capabilities, DecDiffStrategy,
                                         register_method)

    class _DenseDeclaredStrategy(DecDiffStrategy):
        name = "dense-declared"
        capabilities = Capabilities(layouts=("dense",))

    register_method("dense-declared-test", _DenseDeclaredStrategy(),
                    overwrite=True)
    with pytest.raises(ValueError, match="Capabilities record") as ei:
        Experiment(ba_world, "dense-declared-test", layout="sparse")
    assert "('dense',)" in str(ei.value)


def test_capabilities_layouts_validated():
    from repro.engine.strategies import Capabilities

    with pytest.raises(ValueError, match="non-empty subset"):
        Capabilities(layouts=())
    with pytest.raises(ValueError, match="non-empty subset"):
        Capabilities(layouts=("csr",))
    assert Capabilities(layouts=["sparse"]).layouts == ("sparse",)


def test_unknown_layout_rejected(ba_world):
    with pytest.raises(ValueError, match="unknown layout"):
        Experiment(ba_world, "decdiff", layout="csr")


def test_dense_layout_over_big_sparse_topology_refused():
    """layout='dense' forces densification, which the ≤4096-node oracle
    guard refuses at production node counts."""
    st = sparse_ring(4200)
    rng = np.random.default_rng(9)
    xs = [rng.normal(size=(1, 4)).astype(np.float32)] * 4200
    ys = [np.zeros(1, np.int32)] * 4200
    from repro.models.mlp_cnn import make_mlp
    world = World(model=make_mlp(num_classes=2, input_dim=4, hidden=(4,)),
                  topo=st, xs=xs, ys=ys,
                  x_test=rng.normal(size=(4, 4)).astype(np.float32),
                  y_test=np.zeros(4, np.int32))
    with pytest.raises(ValueError, match="refusing to densify"):
        Experiment(world, "decdiff", layout="dense")


def test_layout_inferred_from_topology_type(ba_world):
    exp = Experiment(ba_world, "decdiff",
                     schedule=Schedule(rounds=1, eval_every=1, mode="loop"),
                     **TINY)
    assert exp.layout == "sparse" and exp.sparse_plan is not None
    assert exp.nbr_idx is None
    dense = Experiment(ba_world, "decdiff", layout="dense",
                       schedule=Schedule(rounds=1, eval_every=1,
                                         mode="loop"), **TINY)
    assert dense.layout == "dense" and dense.sparse_plan is None
