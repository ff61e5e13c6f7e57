"""repro.timing contracts: the event clock, its models, and its threading
through the engine.

The load-bearing pins:

  1. degeneracy — `timing=Timing()` with no deadline (zero latency,
     infinite bandwidth, uniform unit step time) is BIT-IDENTICAL to
     `timing=None` — params, bytes, trigger and live histories — across
     methods × transports × backends × layouts × schedule modes (timing
     consumes no rng by construction, so the streams cannot diverge);
  2. arithmetic — the clock is exact: synchronous ticks are the realized
     makespan (slowest node, stretched to the slowest live link's landing
     time when the round exchanges), deadline ticks are exactly
     `deadline`, and `floor(deadline / dt)` caps the local step budget;
  3. lateness — a payload that misses the deadline IS a failed link: the
     per-node stale path masks it via `ever_recv` (delivery history, NOT
     `ever_sent` — the regression this PR fixes), bytes are still burned,
     and making both directions of a pair permanently late is bit-identical
     to scripting that pair out of the graph;
  4. processes — ScriptedGraph replays its mask tables (wrap/clamp) the
     same on both layouts; EnergyChurn integrates the clock's realized
     per-node cost exactly and refuses to run without a Timing;
  5. schedule — loop and scan-fused stay bit-identical with the clock as
     carried state, and the fused program still lowers to ONE lax.scan.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import CommConfig
from repro.dynamics import EnergyChurn, NodeChurn, ScriptedGraph
from repro.engine import Experiment, Schedule, World
from repro.timing import (
    ConstantLink,
    ConstantStep,
    LognormalLink,
    LognormalStep,
    StragglerStep,
    TableLink,
    Timing,
    TimingState,
    TraceStep,
    make_link_model,
    make_node_model,
)

TINY = dict(steps_per_round=4, batch_size=16, lr=0.1, momentum=0.9, seed=3)

# heterogeneous models used whenever the test only needs "some" timing
HET = Timing(node=LognormalStep(sigma=0.5, seed=7),
             link=LognormalLink(seed=9))


@pytest.fixture(scope="module")
def ba_world():
    from repro.models.mlp_cnn import make_mlp

    return World.synthetic(dataset="synth-mnist", nodes=16,
                           topology="barabasi_albert", m=2, seed=3,
                           scale=0.02,
                           model=make_mlp(num_classes=10, hidden=(32,)))


@pytest.fixture(scope="module")
def ring_world():
    from repro.models.mlp_cnn import make_mlp

    return World.synthetic(dataset="synth-mnist", nodes=4, topology="ring",
                           seed=3, scale=0.02,
                           model=make_mlp(num_classes=10, hidden=(32,)))


def _params_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _with(world, **kw):
    return dataclasses.replace(world, **kw)


def _run(world, method="decdiff+vt", rounds=3, **kw):
    args = dict(TINY)
    args.update(kw)
    sched = args.pop("schedule", Schedule(rounds=rounds, eval_every=rounds))
    exp = Experiment(world, method, schedule=sched, **args)
    exp.run()
    return exp


# --------------------------------------------------- 1. degeneracy oracle

def _fingerprint(exp):
    return (tuple(exp.trig_history), exp.comm_bytes_total,
            tuple(exp.live_history))


@pytest.mark.parametrize("mode", ["loop", "fused"])
@pytest.mark.parametrize("layout", ["dense", "sparse"])
@pytest.mark.parametrize("backend", ["vmap", "shard_map"])
def test_degenerate_timing_bit_identical_matrix(ba_world, backend, layout,
                                                mode):
    """Timing() + no deadline == timing=None, bit for bit, on the full
    backend × layout × schedule-mode matrix (16-node BA, per-node int8
    event-triggered transport so the silence path is exercised too)."""
    comm = CommConfig(codec="int8", trigger_threshold=0.3)
    sched = Schedule(rounds=3, eval_every=3, mode=mode)
    ref = _run(ba_world, comm=comm, backend=backend, layout=layout,
               schedule=sched)
    tim = _run(_with(ba_world, timing=Timing()), comm=comm, backend=backend,
               layout=layout, schedule=sched)
    assert _params_equal(ref.params, tim.params)
    assert _fingerprint(ref) == _fingerprint(tim)
    # the degenerate clock still reports: unit step time, B steps/round
    assert tim.sim_time == 3 * TINY["steps_per_round"]
    assert tim.arrived_history == [1.0, 1.0, 1.0]


@pytest.mark.parametrize("method,comm", [
    ("decavg", None),
    ("cfa", None),
    ("cfa-ge", None),          # transport-free (grad-exchange capability)
    ("isol", None),
    ("fedavg", None),
    ("decavg", CommConfig(codec="fp32")),
    ("decdiff+vt", CommConfig(codec="fp32")),
    ("decdiff+vt", CommConfig(codec="int8", trigger_threshold=0.3)),
    ("decdiff+vt", CommConfig(codec="int8", policy="adaptive",
                              target_trigger=0.7, per_edge=True)),
    ("cfa", CommConfig(codec="int8", trigger_threshold=0.3,
                       per_edge=True)),
])
def test_degenerate_timing_bit_identical_methods(ring_world, method, comm):
    """The same oracle across the strategy roster × transport roster."""
    ref = _run(ring_world, method, comm=comm)
    tim = _run(_with(ring_world, timing=Timing()), method, comm=comm)
    assert _params_equal(ref.params, tim.params)
    assert _fingerprint(ref) == _fingerprint(tim)


def test_degenerate_timing_bit_identical_with_dynamics(ring_world):
    """...and composed with a stochastic GraphProcess: the clock consumes
    no rng, so churn realizes identically with and without it."""
    dyn = NodeChurn(p_leave=0.3, p_rejoin=0.6)
    comm = CommConfig(codec="fp32", trigger_threshold=0.3)
    ref = _run(_with(ring_world, dynamics=dyn), comm=comm, rounds=4)
    tim = _run(_with(ring_world, dynamics=dyn, timing=Timing()), comm=comm,
               rounds=4)
    assert _params_equal(ref.params, tim.params)
    assert _fingerprint(ref) == _fingerprint(tim)


# ------------------------------------------------------ 2. clock arithmetic

def test_sync_makespan_is_exact(ring_world):
    """ConstantStep(dt) with a zero-cost link: every synchronous tick is
    exactly B * dt; a nonzero link latency stretches it by the landing
    time; a non-exchanging method (isol) pays compute only."""
    w = _with(ring_world, timing=Timing(node=ConstantStep(2.0)))
    assert _run(w, rounds=3).sim_time == 3 * 4 * 2.0
    w = _with(ring_world, timing=Timing(node=ConstantStep(2.0),
                                        link=ConstantLink(latency=1.5)))
    assert _run(w, rounds=3).sim_time == 3 * (4 * 2.0 + 1.5)
    assert _run(w, "isol", rounds=3).sim_time == 3 * 4 * 2.0


def test_straggler_dominates_sync_makespan(ring_world):
    """StragglerStep: the slow minority sets the synchronous clock."""
    st = StragglerStep(dt=1.0, frac=0.25, factor=8.0, seed=3)
    assert (list(st.slow_nodes(4))
            == [int(np.argmax(np.asarray(st.bind(4)(jnp.int32(0)))))])
    exp = _run(_with(ring_world, timing=Timing(node=st)), rounds=2)
    assert exp.sim_time == 2 * 4 * 8.0


def test_deadline_caps_local_steps_and_ticks(ring_world):
    """Schedule(deadline=2.5) under unit step time: every node trains
    floor(2.5) = 2 of its 4 budgeted steps, the realized per-node cost is
    2.0s, and the clock ticks by exactly the deadline."""
    exp = _run(_with(ring_world, timing=Timing()),
               schedule=Schedule(rounds=3, eval_every=3, deadline=2.5))
    assert exp.sim_time == 3 * 2.5
    assert exp.sim_time_history == [2.5, 5.0, 7.5]
    assert np.asarray(exp.time_state.last_cost).tolist() == [2.0] * 4
    assert exp.arrived_history == [1.0, 1.0, 1.0]


def test_deadline_requires_timing(ring_world):
    with pytest.raises(ValueError, match="needs World\\(timing"):
        Experiment(ring_world, "decdiff+vt",
                   schedule=Schedule(deadline=1.0), **TINY)
    with pytest.raises(ValueError, match="deadline"):
        Schedule(deadline=-1.0)


def test_world_rejects_non_timing(ring_world):
    with pytest.raises(TypeError, match="repro.timing.Timing"):
        Experiment(_with(ring_world, timing=ConstantStep()), "decdiff+vt",
                   **TINY)


# ------------------------------------------------- 3. lateness = link down

def _late_pair_latency(topo, pairs):
    """Canonical [num_directed] latency table: 1e9 on both directions of
    each (lo, hi) pair, 0 elsewhere."""
    if hasattr(topo, "edge_src"):
        src = np.asarray(topo.edge_src)
        dst = np.asarray(topo.edge_dst)
    else:
        dst, src = np.nonzero(topo.adjacency)
    lat = np.zeros(len(src), np.float32)
    for lo, hi in pairs:
        lat[((src == lo) & (dst == hi)) | ((src == hi) & (dst == lo))] = 1e9
    return lat, src, dst


@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_late_edge_is_stale_and_never_recv(ring_world, layout):
    """The silence-path regression pin (on both layouts): a sender whose
    payloads NEVER arrive must not be aggregated under on_silence=stale.
    `ever_sent` flips on send; `ever_recv` — what the stale mask now
    consults — must not.  The aggregation outcome is pinned bit-exactly
    against a world where the late pair simply does not exist."""
    topo = ring_world.topo  # both layouts share the canonical edge order
    lat, src, dst = _late_pair_latency(topo, [(0, 1)])
    late = lat > 0
    tm = Timing(link=TableLink(latency=lat))
    sched = Schedule(rounds=3, eval_every=3, deadline=10.0)
    exp = _run(_with(ring_world, timing=tm), layout=layout,
               comm=CommConfig(codec="fp32", on_silence="stale"),
               schedule=sched)
    st = exp.comm_state
    # everyone transmitted every round (threshold 0)...
    assert np.asarray(st.ever_sent).min() == 1.0
    if layout == "sparse":
        ever = np.asarray(st.ever_recv)
    else:
        # scatter the [N, max_deg] panel to canonical directed-edge order:
        # panel slot e of receiver r is the e-th of r's sender-ascending
        # in-edges — the canonical (dst, src) order restricted to dst == r.
        panel = np.asarray(st.ever_recv)
        slot = np.concatenate([np.arange(np.sum(dst == r))
                               for r in range(topo.num_nodes)])
        ever = panel[dst, slot]
    # ...but the late pair never DELIVERED, everyone else did
    assert (ever[late] == 0.0).all()
    assert (ever[~late] == 1.0).all()
    # bit-identical to the same schedule with the pair scripted out
    cut = np.array(topo.adjacency, np.float32)
    cut[0, 1] = cut[1, 0] = 0.0
    ref = _run(_with(ring_world, timing=Timing(),
                     dynamics=ScriptedGraph(tables=cut[None])),
               layout=layout,
               comm=CommConfig(codec="fp32", on_silence="stale"),
               schedule=sched)
    assert _params_equal(exp.params, ref.params)
    # lateness burns the sender's bytes; a non-existent link carries none
    assert exp.comm_bytes_total > ref.comm_bytes_total


def test_late_edge_arrival_accounting(ring_world):
    """arrived_frac counts exactly the on-time directed edges."""
    lat, _, _ = _late_pair_latency(ring_world.topo, [(0, 1)])
    exp = _run(_with(ring_world, timing=Timing(link=TableLink(latency=lat))),
               schedule=Schedule(rounds=2, eval_every=2, deadline=10.0))
    assert exp.arrived_history == [6.0 / 8.0] * 2


def test_drop_mode_masks_late_edges_too(ring_world):
    """on_silence=drop with one late pair: the late slots carry zero
    aggregation weight but bytes are still burned (same totals as stale —
    byte accounting is sender-side)."""
    lat, _, _ = _late_pair_latency(ring_world.topo, [(0, 1)])
    tm = Timing(link=TableLink(latency=lat))
    sched = Schedule(rounds=3, eval_every=3, deadline=10.0)
    a = _run(_with(ring_world, timing=tm),
             comm=CommConfig(codec="fp32", on_silence="drop"), schedule=sched)
    b = _run(_with(ring_world, timing=tm),
             comm=CommConfig(codec="fp32", on_silence="stale"), schedule=sched)
    assert a.comm_bytes_total == b.comm_bytes_total
    # with threshold 0 every on-time edge re-delivers each round, so stale
    # and drop see identical masks and agree bit-exactly
    assert _params_equal(a.params, b.params)


def test_per_edge_transport_freezes_late_links(ring_world):
    """Per-edge transport: a late link is a failed link — the receiver's
    cache freezes (`ever_delivered` stays 0 on the late pair)."""
    lat, src, dst = _late_pair_latency(ring_world.topo, [(0, 1)])
    exp = _run(_with(ring_world, timing=Timing(link=TableLink(latency=lat))),
               comm=CommConfig(codec="fp32", per_edge=True),
               schedule=Schedule(rounds=3, eval_every=3, deadline=10.0))
    panel = np.asarray(exp.comm_state.ever_delivered)  # [N, max_deg]
    slot = np.concatenate([np.arange(np.sum(dst == r)) for r in range(4)])
    ever = panel[dst, slot]
    assert (ever[lat > 0] == 0.0).all()
    assert (ever[lat == 0] == 1.0).all()


# ------------------------------------------------------------ 4. processes

def test_scripted_graph_wrap_and_clamp(ring_world):
    """A [2, N, N] table under both past-end rules: wrap replays 0,1,0,1...;
    clamp holds the last row."""
    n = 4
    full = np.zeros((n, n), np.float32)
    idx = np.arange(n)
    full[idx, (idx + 1) % n] = full[(idx + 1) % n, idx] = 1.0
    half = np.array(full)
    half[0, 1] = half[1, 0] = half[2, 3] = half[3, 2] = 0.0
    tables = np.stack([full, half])
    for rule, want in [("wrap", [1.0, 0.5, 1.0, 0.5]),
                       ("clamp", [1.0, 0.5, 0.5, 0.5])]:
        exp = _run(_with(ring_world,
                         dynamics=ScriptedGraph(tables=tables,
                                                past_end=rule)), rounds=4)
        assert exp.live_history == want, rule


def test_scripted_graph_dense_sparse_parity(ring_world):
    """The same table replays identically on both layouts (params + live
    history), like every other GraphProcess."""
    n = 4
    full = np.zeros((n, n), np.float32)
    idx = np.arange(n)
    full[idx, (idx + 1) % n] = full[(idx + 1) % n, idx] = 1.0
    half = np.array(full)
    half[0, 1] = half[1, 0] = 0.0
    dyn = ScriptedGraph(tables=np.stack([full, half]))
    runs = {lay: _run(_with(ring_world, dynamics=dyn), layout=lay, rounds=4)
            for lay in ("dense", "sparse")}
    assert _params_equal(runs["dense"].params, runs["sparse"].params)
    assert runs["dense"].live_history == runs["sparse"].live_history


def test_scripted_graph_validation():
    with pytest.raises(ValueError, match="past_end"):
        ScriptedGraph(tables=np.ones((1, 2, 2)), past_end="loop")
    with pytest.raises(ValueError, match="\\{0, 1\\}"):
        ScriptedGraph(tables=np.full((1, 2, 2), 0.5))
    with pytest.raises(ValueError, match="square"):
        ScriptedGraph(tables=np.ones((1, 2, 3)))
    asym = np.zeros((1, 3, 3), np.float32)
    asym[0, 0, 1] = 1.0
    sg = ScriptedGraph(tables=asym)
    from repro.graphs import make_topology
    with pytest.raises(ValueError, match="symmetric"):
        sg.bind(make_topology("ring", n=3))


def test_energy_churn_integrates_realized_cost(ring_world):
    """EnergyChurn under ConstantStep(1.0), B=4 (realized cost 4.0/round
    while alive, observed one round late): capacity 9, recharge 3,
    rejoin_at 4 gives the exact schedule
      r0: obs=0  e=9  alive     r3: obs=4  e=clip(1-4)=0  dies
      r1: obs=4  e=5  alive     r4: e=0+3=3 < 4           dead
      r2: obs=4  e=1  alive     r5: e=3+3=6 >= 4          rejoins
    The rejoin round itself recharges (the transition runs BEFORE
    training), so the final energy is 6 — the drain for its 4 trained
    steps would land at a round 6 that never runs."""
    dyn = EnergyChurn(capacity=9.0, recharge=3.0, rejoin_at=4.0)
    exp = _run(_with(ring_world, timing=Timing(), dynamics=dyn), rounds=6)
    assert exp.live_history == [1.0, 1.0, 1.0, 0.0, 0.0, 1.0]
    energy, alive = exp.dyn_state
    assert np.asarray(alive).tolist() == [1.0] * 4
    assert np.asarray(energy).tolist() == [6.0] * 4
    # the clock only billed the alive rounds: 4 alive rounds x 4 steps
    assert exp.sim_time == 4 * 4.0
    assert np.asarray(exp.time_state.last_cost).tolist() == [4.0] * 4


def test_energy_churn_requires_timing(ring_world):
    with pytest.raises(ValueError, match="observes the event clock"):
        Experiment(_with(ring_world, dynamics=EnergyChurn()), "decdiff+vt",
                   **TINY)


def test_energy_churn_validation():
    with pytest.raises(ValueError, match="capacity"):
        EnergyChurn(capacity=0.0)
    with pytest.raises(ValueError, match="rejoin_at"):
        EnergyChurn(capacity=4.0, rejoin_at=5.0)


# --------------------------------------------------------------- 5. models

def test_trace_step_wrap_and_clamp():
    table = np.array([[1.0, 2.0], [3.0, 4.0]], np.float32)
    wrap = TraceStep(table=table).bind(2)
    clamp = TraceStep(table=table, past_end="clamp").bind(2)
    assert np.asarray(wrap(jnp.int32(4))).tolist() == [1.0, 2.0]
    assert np.asarray(clamp(jnp.int32(4))).tolist() == [3.0, 4.0]
    with pytest.raises(ValueError, match="positive"):
        TraceStep(table=np.zeros((2, 2)))
    with pytest.raises(ValueError, match="2 nodes"):
        TraceStep(table=table).bind(3)


def test_lognormal_models_deterministic_by_seed(ring_world):
    a = LognormalStep(sigma=0.5, seed=7).bind(8)(0)
    b = LognormalStep(sigma=0.5, seed=7).bind(8)(5)
    assert np.array_equal(np.asarray(a), np.asarray(b))  # static per node
    c = LognormalStep(sigma=0.5, seed=8).bind(8)(0)
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    topo = ring_world.topo
    t1 = LognormalLink(seed=9).bind(topo, 100.0)
    t2 = LognormalLink(seed=9).bind(topo, 100.0)
    assert np.array_equal(t1, t2)
    # per-PAIR draws: both directions of a link price identically
    lat, src, dst = _late_pair_latency(topo, [])
    fwd = (src == 0) & (dst == 1)
    rev = (src == 1) & (dst == 0)
    assert t1[fwd] == t1[rev]


def test_link_model_validation(ring_world):
    topo = ring_world.topo
    with pytest.raises(ValueError, match="latency"):
        ConstantLink(latency=-1.0).bind(topo, 4.0)
    with pytest.raises(ValueError, match="bandwidth"):
        ConstantLink(bandwidth=0.0).bind(topo, 4.0)
    with pytest.raises(ValueError, match="directed"):
        TableLink(latency=np.zeros(3)).bind(topo, 4.0)
    # bytes / bandwidth prices the wire exactly
    t = ConstantLink(latency=1.0, bandwidth=8.0).bind(topo, 16.0)
    assert (t == 3.0).all()


def test_registries():
    assert isinstance(make_node_model("straggler", frac=0.5), StragglerStep)
    assert isinstance(make_link_model("table"), TableLink)
    with pytest.raises(ValueError, match="unknown"):
        make_node_model("warp")
    with pytest.raises(ValueError, match="unknown"):
        make_link_model("warp")


# ------------------------------------------------------------- 6. schedule

def test_loop_fused_bit_identical_with_deadline(ba_world):
    """The clock rides the scan carry: loop and fused agree bit-exactly on
    params AND the full time/arrival accounting, heterogeneous models,
    per-node transport, deadline ticks."""
    runs = {}
    for mode in ("loop", "fused"):
        runs[mode] = _run(
            _with(ba_world, timing=HET),
            comm=CommConfig(codec="int8", trigger_threshold=0.3),
            schedule=Schedule(rounds=4, eval_every=2, deadline=4.0,
                              mode=mode))
    a, b = runs["loop"], runs["fused"]
    assert _params_equal(a.params, b.params)
    assert _fingerprint(a) == _fingerprint(b)
    assert a.sim_time_history == b.sim_time_history
    assert a.arrived_history == b.arrived_history
    assert np.array_equal(np.asarray(a.time_state.t),
                          np.asarray(b.time_state.t))


def test_fused_program_is_one_scan(ring_world):
    """The whole K-round schedule with the clock enabled still lowers to
    exactly ONE lax.scan (plus the per-round local-training scans nested
    INSIDE its body — we count only top-level scan equations)."""
    exp = Experiment(_with(ring_world, timing=HET), "decdiff+vt",
                     comm=CommConfig(codec="int8", trigger_threshold=0.3),
                     schedule=Schedule(rounds=4, eval_every=2, deadline=4.0),
                     **TINY)
    fused = exp._fused_program(4, 2)
    carry = ((exp.params, exp.opt_state) + exp._get_states() + (exp.rng,))
    jaxpr = jax.make_jaxpr(lambda c: fused(c))(carry)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    jits = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "jit"]
    if jits:  # the jitted program wraps the scan one level down
        inner = jits[0].params["jaxpr"].jaxpr
        scans = [e for e in inner.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1


def test_dense_sparse_parity_with_deadline(ba_world):
    """Both layouts agree bit-exactly under heterogeneous timing with a
    deadline (participation=1: no layout-shaped draws)."""
    runs = {lay: _run(_with(ba_world, timing=HET), layout=lay,
                      schedule=Schedule(rounds=3, eval_every=3, deadline=4.0))
            for lay in ("dense", "sparse")}
    assert _params_equal(runs["dense"].params, runs["sparse"].params)
    assert (runs["dense"].sim_time_history
            == runs["sparse"].sim_time_history)
    assert runs["dense"].arrived_history == runs["sparse"].arrived_history


def test_vmap_shardmap_parity_with_deadline(ba_world):
    runs = {be: _run(_with(ba_world, timing=HET), backend=be,
                     comm=CommConfig(codec="int8", policy="adaptive",
                                     target_trigger=0.7, per_edge=True),
                     schedule=Schedule(rounds=3, eval_every=3, deadline=4.0))
            for be in ("vmap", "shard_map")}
    assert _params_equal(runs["vmap"].params, runs["shard_map"].params)
    assert runs["vmap"].sim_time_history == runs["shard_map"].sim_time_history
    assert runs["vmap"].arrived_history == runs["shard_map"].arrived_history


# ------------------------------------------------------------ property lane

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings

    HAVE_HYP = True
except Exception:  # pragma: no cover
    HAVE_HYP = False

if HAVE_HYP:

    @pytest.mark.fuzz
    @settings(deadline=None, max_examples=10)
    @given(sigma=st.floats(0.0, 1.5), seed=st.integers(0, 2 ** 16),
           dl=st.floats(0.5, 8.0))
    def test_fuzz_clock_invariants(sigma, seed, dl):
        """For any lognormal node/link draw and any deadline: sim_time is
        strictly increasing by exactly the deadline per round, arrived
        fractions live in [0, 1], realized costs are nonneg and at most
        the deadline cap, and params stay finite."""
        from repro.models.mlp_cnn import make_mlp

        world = World.synthetic(
            dataset="synth-mnist", nodes=4, topology="ring", seed=3,
            scale=0.02, model=make_mlp(num_classes=10, hidden=(16,)),
            timing=Timing(node=LognormalStep(sigma=sigma, seed=seed),
                          link=LognormalLink(seed=seed + 1)))
        exp = Experiment(world, "decdiff+vt",
                         schedule=Schedule(rounds=3, eval_every=3,
                                           deadline=dl),
                         steps_per_round=2, batch_size=8, lr=0.1,
                         momentum=0.9, seed=1)
        exp.run()
        ts = np.asarray(exp.sim_time_history)
        assert np.allclose(np.diff(np.concatenate([[0.0], ts])), dl)
        assert all(0.0 <= a <= 1.0 for a in exp.arrived_history)
        cost = np.asarray(exp.time_state.last_cost)
        dt = np.asarray(exp.bound_timing.step_time(jnp.int32(2)))
        assert (cost >= 0).all() and (cost <= dl + 1e-5).all()
        assert (cost <= 2 * dt + 1e-5).all()
        assert all(np.isfinite(np.asarray(p)).all()
                   for p in jax.tree.leaves(exp.params))

    @pytest.mark.fuzz
    @settings(deadline=None, max_examples=20)
    @given(t=st.integers(1, 5), n=st.integers(2, 12),
           r=st.integers(0, 40))
    def test_fuzz_past_end_rules(t, n, r):
        from repro.timing.models import past_end_index

        assert int(past_end_index(jnp.int32(r), t, "wrap")) == r % t
        assert int(past_end_index(jnp.int32(r), t, "clamp")) == min(r, t - 1)
