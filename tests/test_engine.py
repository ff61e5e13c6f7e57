"""repro.engine contracts: the strategy registry, the Capabilities record,
and backend/schedule equivalence.

The load-bearing pins:

  1. registry — unknown methods fail with the available roster in the
     message; custom strategies registered through `register_method` run
     end-to-end through the same engine as the built-ins; inconsistent
     capability declarations fail AT REGISTRATION, with the roster;
  2. schedule — the scan-fused runner produces bit-identical params and
     metrics to the per-round Python loop (same rng stream, same ops,
     compiled once under `lax.scan`);
  3. backends — the shard_map lowering is bit-identical to the vmap
     lowering for EVERY declared capability (plain, per-node transport,
     per-edge adaptive transport, CFA-GE gradient exchange), on both wires
     (encoded payload / decoded rows), single-pod here and on the forced
     4-device mesh in tests/test_exchange_unified.py;
  4. dynamics × server — FedAvg under churn aggregates LIVE clients only
     (the offline-clients-frozen-params regression).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import CommConfig
from repro.engine import (
    AggregationStrategy,
    Capabilities,
    Experiment,
    Schedule,
    TrainConfig,
    World,
    available_methods,
    build_round,
    get_method,
    register_method,
)
from repro.engine.strategies import _REGISTRY


@pytest.fixture(scope="module")
def tiny_world():
    """4-node ring over a reduced synth-mnist; small MLP."""
    from repro.models.mlp_cnn import make_mlp

    return World.synthetic(dataset="synth-mnist", nodes=4, topology="ring",
                           seed=3, scale=0.02,
                           model=make_mlp(num_classes=10, hidden=(32,)))


TINY = dict(steps_per_round=2, batch_size=16, lr=0.1, momentum=0.9, seed=3)


def _exp(world, method="decdiff+vt", rounds=3, mode="loop", **kw):
    kw = {**TINY, **kw}
    return Experiment(world, method,
                      schedule=Schedule(rounds=rounds, eval_every=2,
                                        mode=mode), **kw)


def _params_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ----------------------------------------------------------------- registry


def test_unknown_method_error_lists_available():
    with pytest.raises(ValueError) as ei:
        get_method("decdfif+vt")  # typo'd
    msg = str(ei.value)
    assert "unknown method 'decdfif+vt'" in msg
    for name in available_methods():
        assert name in msg  # the full roster is in the message


def test_paper_roster_is_registered():
    roster = available_methods()
    for m in ("isol", "fedavg", "decavg", "dechetero", "cfa", "cfa-ge",
              "decdiff", "decdiff+vt"):
        assert m in roster
    spec = get_method("decdiff+vt")
    assert spec.loss == "vt" and not spec.common_init
    assert spec.strategy.supports_transport
    assert not get_method("cfa-ge").strategy.supports_transport
    assert get_method("fedavg").common_init


def test_register_method_guards():
    with pytest.raises(ValueError, match="already registered"):
        register_method("decdiff", get_method("decdiff").strategy)
    with pytest.raises(TypeError, match="AggregationStrategy"):
        register_method("not-a-strategy", lambda: None)


# ------------------------------------------------------------- capabilities


def test_capabilities_record_is_frozen_and_validated():
    caps = Capabilities()
    assert caps.kind == "gossip" and not caps.grad_exchange
    assert caps.transport  # plain model-gossip rides the comm transport
    with pytest.raises(Exception):
        caps.kind = "server"  # frozen
    with pytest.raises(ValueError, match="kind"):
        Capabilities(kind="peer-to-peer")
    with pytest.raises(ValueError, match="grad_exchange"):
        Capabilities(kind="server", grad_exchange=True)
    # the derived transport capability across the roster
    assert not Capabilities(kind="server").transport
    assert not Capabilities(kind="none").transport
    assert not Capabilities(grad_exchange=True).transport


def test_roster_capabilities_are_consistent():
    """Every registered strategy's legacy views delegate to its record."""
    for name in available_methods():
        s = get_method(name).strategy
        caps = s.capabilities
        assert isinstance(caps, Capabilities), name
        assert (s.kind, s.grad_exchange, s.supports_transport) == \
            (caps.kind, caps.grad_exchange, caps.transport), name
    assert get_method("cfa-ge").strategy.capabilities.grad_exchange
    assert get_method("fedavg").strategy.capabilities.kind == "server"
    assert get_method("isol").strategy.capabilities.kind == "none"


def test_register_method_rejects_shadowed_capabilities():
    """A subclass that shadows the derived views with stale class attrs
    (the pre-Capabilities declaration style) must fail at registration —
    with the roster in the message — not silently lower the wrong path."""

    class _Shadowed(AggregationStrategy):
        name = "shadowed"
        kind = "server"  # shadows the capabilities-delegating property

        def aggregate(self, exp, state, params, gathered, mask):
            return params

    with pytest.raises(ValueError, match="shadow") as ei:
        register_method("shadowed-test", _Shadowed())
    assert "decdiff" in str(ei.value)  # the roster is in the message

    class _NotARecord(AggregationStrategy):
        name = "notarecord"
        capabilities = {"kind": "gossip"}

        def aggregate(self, exp, state, params, gathered, mask):
            return params

    with pytest.raises(TypeError, match="Capabilities"):
        register_method("notarecord-test", _NotARecord())
    assert "shadowed-test" not in _REGISTRY
    assert "notarecord-test" not in _REGISTRY


def test_transport_error_lists_capable_roster(tiny_world):
    """The build-time capability error names the methods that DO support
    the transport, so the fix is in the message."""
    with pytest.raises(ValueError, match="model-gossip only") as ei:
        Experiment(tiny_world, "cfa-ge", comm=CommConfig(codec="fp32"))
    msg = str(ei.value)
    for m in ("'decdiff'", "'decdiff+vt'", "'dechetero'", "'cfa'"):
        assert m in msg
    assert "'cfa-ge'" not in msg.split("transport-capable")[1]


class _HeadroomStrategy(AggregationStrategy):
    """A deliberately-custom gossip rule: move each node a fixed fraction
    toward the plain delivered-neighbour mean (no data-size weighting).
    Exists to prove third-party strategies run the whole engine unchanged —
    including the transport, which it supports by capability."""

    name = "headroom"

    def __init__(self, alpha=0.5):
        self.alpha = alpha

    def init_state(self, exp):
        return {"valid": exp.nbr_valid}

    def aggregate(self, exp, state, params, gathered, mask):
        a = self.alpha

        def one(local, stacked, m):
            tot = jnp.maximum(jnp.sum(m), 1.0)
            gate = (jnp.sum(m) > 0).astype(jnp.float32)

            def leaf(li, st):
                mb = m.reshape(m.shape + (1,) * (st.ndim - 1))
                avg = jnp.sum(mb * st.astype(jnp.float32), axis=0) / tot
                lf = li.astype(jnp.float32)
                return (lf + gate * a * (avg - lf)).astype(li.dtype)

            return jax.tree.map(leaf, local, stacked)

        return jax.vmap(one, in_axes=(0, 0, 0))(
            params, gathered, state["valid"] * mask)


def test_custom_strategy_end_to_end(tiny_world):
    """The satellite contract: a registered custom strategy runs the full
    engine (local SGD, exchange, aggregation, eval, and the gossip
    transport selected purely off its capability)."""
    name = "headroom-test"
    register_method(name, _HeadroomStrategy(alpha=0.5), loss="vt")
    try:
        exp = _exp(tiny_world, name, rounds=3, mode="fused")
        hist = exp.run()
        assert np.isfinite(hist[-1].acc_mean)
        iso = _exp(tiny_world, "isol", rounds=3, mode="fused")
        iso.run()
        # gossip genuinely ran: differs from no-communication training
        assert not _params_equal(exp.params, iso.params)
        # capability-selected transport: same custom method, now with the
        # fp32/thr0/fixed transport in the middle — bit-for-bit equal
        comm = Experiment(tiny_world, name,
                          comm=CommConfig(codec="fp32"),
                          schedule=Schedule(rounds=3, eval_every=2,
                                            mode="fused"), **TINY)
        comm.run()
        assert comm.transport is not None
        assert _params_equal(exp.params, comm.params)
    finally:
        _REGISTRY.pop(name, None)


# ------------------------------------------------------ config / validation


def test_schedule_and_backend_validation(tiny_world):
    with pytest.raises(ValueError, match="schedule mode"):
        Schedule(rounds=3, mode="warp")
    with pytest.raises(ValueError, match="unknown backend"):
        Experiment(tiny_world, "decdiff+vt", backend="pmap")
    with pytest.raises(ValueError, match="unknown method"):
        Experiment(tiny_world, "decdiffff")
    with pytest.raises(ValueError, match="model-gossip only"):
        Experiment(tiny_world, "isol", comm=CommConfig(codec="fp32"))
    with pytest.raises(TypeError):
        Experiment(tiny_world, "decdiff+vt", warp_factor=9)


def test_shardmap_lowers_every_capability(tiny_world):
    """The configurations that historically raised at build time on the
    sharded backend — per-edge (adaptive) transport and CFA-GE gradient
    exchange — now lower through the unified exchange and match vmap
    bit-for-bit (single-pod here; real 4-pod axis in
    tests/test_exchange_unified.py)."""
    for method, comm in (
        ("decdiff+vt", CommConfig(codec="int8", per_edge=True,
                                  trigger_threshold=1.0)),
        ("dechetero", CommConfig(codec="int8", policy="adaptive",
                                 target_trigger=0.5)),
        ("cfa-ge", None),
    ):
        exps = []
        for backend in ("vmap", "shard_map"):
            exp = Experiment(tiny_world, method, comm=comm, backend=backend,
                             schedule=Schedule(rounds=3, eval_every=3,
                                               mode="loop"), **TINY)
            exp.run()
            exps.append(exp)
        assert _params_equal(exps[0].params, exps[1].params), method
        assert exps[0].comm_bytes_total == exps[1].comm_bytes_total, method
        assert exps[0].trig_history == exps[1].trig_history, method


def test_wire_validation_and_bit_identity(tiny_world):
    """`wire=` must validate; the encoded-payload gather (the default) and
    the decoded-rows oracle wire carry the same information.  Decode is
    deterministic, so a single exchange step is bitwise identical across
    wires (asserted at op level below); end-to-end the two builds are
    distinct XLA programs whose fusion may differ in the last ulp, so
    params compare at ulp tolerance while the integer-valued accounting
    (bytes, trigger history) must match exactly."""
    with pytest.raises(ValueError, match="unknown wire"):
        Experiment(tiny_world, "decdiff+vt", wire="telepathy")

    # op level: one exchange step, both wires, bitwise equal.
    from repro.comm.transport import GossipTransport

    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.standard_normal((8, 33)), jnp.float32)}
    tr = GossipTransport(CommConfig(codec="int8", trigger_threshold=1.0),
                         params)
    st = tr.init_state(params)
    st = st._replace(last_sent=jnp.asarray(
        rng.standard_normal(st.last_sent.shape), jnp.float32))
    key = jax.random.PRNGKey(3)
    step = {w: jax.jit(lambda p, s, k, w=w: tr.exchange(p, s, k, wire=w))(
        params, st, key) for w in ("encoded", "decoded")}
    for a, b in zip(jax.tree.leaves(step["encoded"]),
                    jax.tree.leaves(step["decoded"])):
        assert jnp.array_equal(a, b)

    comm = CommConfig(codec="int8", trigger_threshold=1.0)
    exps = []
    for wire in ("encoded", "decoded"):
        exp = Experiment(tiny_world, "decdiff+vt", comm=comm,
                         backend="shard_map", wire=wire,
                         schedule=Schedule(rounds=3, eval_every=3,
                                           mode="loop"),
                         participation=0.7, **TINY)
        exp.run()
        exps.append(exp)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=3e-6, atol=1e-7),
        exps[0].params, exps[1].params)
    assert exps[0].comm_bytes_total == exps[1].comm_bytes_total
    assert exps[0].trig_history == exps[1].trig_history


def test_train_config_immutable_and_overridable(tiny_world):
    exp = _exp(tiny_world, rounds=2, lr=0.05)
    assert exp.train.lr == 0.05
    assert TrainConfig().lr == 1e-3  # defaults untouched
    with pytest.raises(Exception):
        exp.train.lr = 0.1  # frozen


# --------------------------------------------------- schedule equivalence


def test_fused_schedule_bitexact_vs_loop(tiny_world):
    """The scan-fused runner (one jitted program for K rounds + gated
    evals) must reproduce the per-round loop bit-for-bit: params, eval
    cadence, metrics, and — through the transport — the byte accounting."""
    comm = CommConfig(codec="fp32", trigger_threshold=0.0)
    loop = Experiment(tiny_world, "decdiff+vt", comm=comm,
                      schedule=Schedule(rounds=5, eval_every=2, mode="loop"),
                      participation=0.7, **TINY)
    hl = loop.run()
    fused = Experiment(tiny_world, "decdiff+vt", comm=comm,
                       schedule=Schedule(rounds=5, eval_every=2,
                                         mode="fused"),
                       participation=0.7, **TINY)
    hf = fused.run()
    assert _params_equal(loop.params, fused.params)
    assert [m.round for m in hl] == [m.round for m in hf] == [0, 2, 4]
    for a, b in zip(hl, hf):
        assert np.array_equal(a.acc_per_node, b.acc_per_node)
        assert np.array_equal(a.loss_per_node, b.loss_per_node)
        assert a.bytes_on_wire == b.bytes_on_wire
        assert a.triggered_frac == b.triggered_frac
    assert loop.comm_bytes_total == fused.comm_bytes_total > 0
    assert loop.trig_history == fused.trig_history


def test_fused_schedule_continues_across_runs(tiny_world):
    """Repeated run() calls continue from the evolved state in both modes
    (the legacy contract benchmarks rely on for warmup-then-measure)."""
    a = _exp(tiny_world, rounds=2, mode="loop")
    a.run()
    a.run()
    b = _exp(tiny_world, rounds=2, mode="fused")
    b.run()
    b.run()
    assert _params_equal(a.params, b.params)



def test_compile_ahead_serves_the_fused_run(tiny_world):
    """`compile()` AOT-builds the program `run()` dispatches: compiling
    twice returns the same executable, and a run after it is bit-equal to
    a run that compiled on first call."""
    a = _exp(tiny_world, rounds=3, mode="fused")
    compiled = a.compile()
    assert a.compile() is compiled
    assert " while(" in compiled.as_text()  # the rounds loop
    ha = a.run()
    b = _exp(tiny_world, rounds=3, mode="fused")
    hb = b.run()
    assert _params_equal(a.params, b.params)
    for x, y in zip(ha, hb):
        assert np.array_equal(x.acc_per_node, y.acc_per_node)

# -------------------------------------------------- backend equivalence


@pytest.mark.multihost
@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs >= 4 devices for a real pod axis")
def test_vmap_shardmap_scanfused_bit_identical(tiny_world):
    """The ISSUE-4 acceptance pin: the same decdiff+vt spec (with the
    fp32/threshold-0/fixed comm) lowered to vmap, to shard_map over the
    4-pod CPU mesh, and scan-fused on top, yields bit-identical params."""
    comm = CommConfig(codec="fp32", trigger_threshold=0.0)
    runs = {}
    for backend in ("vmap", "shard_map"):
        for mode in ("loop", "fused"):
            exp = Experiment(tiny_world, "decdiff+vt", comm=comm,
                             backend=backend,
                             schedule=Schedule(rounds=3, eval_every=2,
                                               mode=mode), **TINY)
            hist = exp.run()
            runs[(backend, mode)] = (exp, hist)
    ref, ref_hist = runs[("vmap", "loop")]
    assert ref.mesh is None  # the vmap lowering is mesh-free
    for key, (exp, hist) in runs.items():
        assert _params_equal(ref.params, exp.params), key
        assert ref.comm_bytes_total == exp.comm_bytes_total, key
        assert ref.trig_history == exp.trig_history, key
        for a, b in zip(ref_hist, hist):
            assert np.array_equal(a.acc_per_node, b.acc_per_node), key
    smap = runs[("shard_map", "loop")][0]
    assert int(smap.mesh.shape["pod"]) == 4  # a real 4-pod axis was used


@pytest.mark.multihost
@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs >= 4 devices for a real pod axis")
def test_shardmap_event_triggered_int8_matches_vmap(tiny_world):
    """Beyond the acceptance floor: the per-NODE transport with a real
    codec + trigger also lowers to shard_map bit-identically (state rows
    shard with their nodes; gates/caches cross pods via all_gather)."""
    comm = CommConfig(codec="int8", trigger_threshold=1.0, stochastic=True)
    exps = []
    for backend in ("vmap", "shard_map"):
        exp = Experiment(tiny_world, "decdiff+vt", comm=comm,
                         backend=backend,
                         schedule=Schedule(rounds=4, eval_every=10,
                                           mode="fused"),
                         participation=0.7, **TINY)
        exp.run()
        exps.append(exp)
    assert _params_equal(exps[0].params, exps[1].params)
    assert exps[0].trig_history == exps[1].trig_history
    assert np.array_equal(np.asarray(exps[0].comm_state.last_sent),
                          np.asarray(exps[1].comm_state.last_sent))


def test_shardmap_single_pod_matches_vmap(tiny_world):
    """On a single-device host the shard_map lowering degenerates to one
    pod and must still match vmap exactly (so the backend is exercised
    everywhere, not only in the multihost CI lane)."""
    ref = _exp(tiny_world, rounds=2, mode="loop")
    ref.run()
    smap = Experiment(tiny_world, "decdiff+vt", backend="shard_map",
                      schedule=Schedule(rounds=2, eval_every=2, mode="loop"),
                      **TINY)
    smap.run()
    assert _params_equal(ref.params, smap.params)


def test_build_round_signature_matches_transport(tiny_world):
    """build_round is the public lowering hook: its calling convention is
    (params, opt, [comm_state,] round_idx, rng)."""
    exp = _exp(tiny_world, rounds=1)
    fn = build_round(exp)
    out = fn(exp.params, exp.opt_state, jnp.int32(0), exp.rng)
    assert len(out) == 4  # params, opt, rng, loss
    cexp = Experiment(tiny_world, "decdiff+vt",
                      comm=CommConfig(codec="fp32"),
                      schedule=Schedule(rounds=1, eval_every=1), **TINY)
    cfn = build_round(cexp)
    out = cfn(cexp.params, cexp.opt_state, cexp.comm_state, jnp.int32(0),
              cexp.rng)
    assert len(out) == 7  # + comm_state, sent_edges, trig_frac


# ------------------------------------------------ server-under-churn bugfix


def _node0_dead():
    """A deterministic process: node 0 is offline every round (never having
    been alive, nothing ever 'rejoins').  Minimal churn fixture for the
    FedAvg liveness regression."""
    from repro.dynamics import GraphEvent, GraphProcess

    class _P(GraphProcess):
        name = "node0-dead"
        needs_rng = False

        def make_step(self, topo):
            idx = jnp.asarray(np.maximum(topo.neighbor_idx, 0))
            valid = jnp.asarray(topo.neighbor_mask.astype(np.float32))
            n = topo.num_nodes
            alive = jnp.ones((n,), jnp.float32).at[0].set(0.0)
            live = valid * alive[:, None] * alive[idx]
            zeros = jnp.zeros((n,), jnp.float32)

            def step(state, round_idx, key):
                del round_idx, key
                return state, GraphEvent(live=live, alive=alive,
                                         rejoined=zeros)

            return step

    return _P()


def test_fedavg_under_churn_averages_live_clients_only(tiny_world):
    """The regression: a churned-out client's frozen params must carry ZERO
    weight in the server average.  fedavg uses common init, so isolating
    the bug is exact: run the same world with node 0 permanently offline,
    recover the post-training pre-aggregation models from an identically-
    seeded no-aggregation run (same init keys, same rng stream through
    local training), and check the engine's round equals the data-size-
    weighted average over the LIVE clients — and NOT the buggy all-clients
    average that would drag in node 0's never-trained init."""
    import dataclasses as _dc

    from repro.core.aggregation import fedavg_aggregate

    world = _dc.replace(tiny_world, dynamics=_node0_dead())
    exp = Experiment(world, "fedavg",
                     schedule=Schedule(rounds=1, eval_every=1, mode="loop"),
                     **TINY)
    counts = np.asarray(exp.counts, np.float32)
    exp.run()

    # the trained-but-unaggregated models, via a common-init isolation twin
    # (identical init keys and rng stream up to the aggregation step)
    name = "isol-coordinated-test"
    register_method(name, get_method("isol").strategy, common_init=True)
    try:
        twin = Experiment(_dc.replace(tiny_world, dynamics=_node0_dead()),
                          name,
                          schedule=Schedule(rounds=1, eval_every=1,
                                            mode="loop"), **TINY)
        p0 = jax.tree.map(np.asarray, twin.params)
        twin.run()
    finally:
        _REGISTRY.pop(name, None)

    alive = np.asarray([0.0, 1.0, 1.0, 1.0], np.float32)
    want_live = fedavg_aggregate(twin.params, jnp.asarray(counts * alive))
    buggy = fedavg_aggregate(twin.params, jnp.asarray(counts))
    got = jax.tree.map(np.asarray, exp.params)
    for g, w, b, init in zip(jax.tree.leaves(got),
                             jax.tree.leaves(want_live),
                             jax.tree.leaves(buggy),
                             jax.tree.leaves(p0)):
        for i in (1, 2, 3):  # live clients hold the live-only average
            assert np.array_equal(g[i], np.asarray(w))
            assert not np.array_equal(g[i], np.asarray(b))
        assert np.array_equal(g[0], init[0])  # the dead client froze
