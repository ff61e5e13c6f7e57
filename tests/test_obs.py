"""repro.obs contracts: telemetry channels, the run ledger, and trace
export.

The load-bearing pins:

  1. degeneracy — `telemetry=None` is BIT-IDENTICAL to a run with every
     channel on: params, bytes, trigger/live histories and the simulated
     clock agree across backends × layouts × schedule modes (the obs
     state rides the scan carry and consumes no rng by construction);
  2. arithmetic — channels are exact, not sampled: with an fp32 codec and
     a zero threshold every live edge fires every round, so
     `edge_trigger == rounds` per edge, `sum(edge_bytes)` equals the
     engine's own `bytes_on_wire` accounting to the last byte,
     `node_steps == rounds * steps_per_round`, staleness is zero, and
     the drift probe is symmetric in (src, dst);
  3. parity — the materialized detail dict is identical (canonical
     (dst, src) edge order) across dense/sparse, vmap/shard_map and
     loop/fused, so a probe value never depends on the execution engine;
  4. one-scan — the fused schedule with ALL channels on still lowers to
     exactly ONE top-level lax.scan;
  5. ledger/trace — the JSONL ledger round-trips through its schema
     validator with the manifest first, the verbose console line is
     byte-stable against the pre-ledger format, and the exported Chrome
     trace's per-edge transfer spans sum EXACTLY to bytes_on_wire;
  6. performance spans — the compiled round carries the five `dfl.*`
     named scopes on every layout and transport, compile() counts its
     lowering and its one compile request, a warm run() counts none, and
     a channel-free profile binds no telemetry state.
"""
import dataclasses
import glob
import json
import re

import jax
import numpy as np
import pytest

from repro.comm import CommConfig
from repro.engine import Experiment, Schedule, World
from repro.fl.metrics import (RoundMetrics, accuracy_table,
                              characteristic_time)
from repro.obs import (CHANNELS, SCOPES, Telemetry, available_channels,
                       build_trace, channels_for, counter_diff, counters,
                       export_trace, format_round, read_ledger, span,
                       span_table, validate_ledger, validate_record)
from repro.timing import LognormalLink, LognormalStep, Timing

TINY = dict(steps_per_round=4, batch_size=16, lr=0.1, momentum=0.9, seed=3)

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


def _with(world, **kw):
    return dataclasses.replace(world, **kw)


def _params_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _fingerprint(exp):
    return (tuple(exp.trig_history), exp.comm_bytes_total,
            tuple(exp.live_history), tuple(exp.sim_time_history))


def _run(world, method="decdiff+vt", **kw):
    args = dict(TINY)
    args.update(kw)
    sched = args.pop("schedule")
    exp = Experiment(world, method, schedule=sched, **args)
    hist = exp.run()
    return exp, hist


def _detail_equal(a, b):
    assert sorted(a) == sorted(b)
    return all(np.allclose(a[k], b[k], rtol=0, atol=0) for k in a)


# --------------------------------------------------- 1. degeneracy oracle

@pytest.mark.parametrize("mode", ["loop", "fused"])
@pytest.mark.parametrize("layout", ["dense", "sparse"])
@pytest.mark.parametrize("backend", ["vmap", "shard_map"])
def test_telemetry_off_bit_identical_matrix(ba_world, backend, layout, mode):
    """All channels on == telemetry=None, bit for bit, on the full
    backend × layout × mode matrix (params AND every accounting stream:
    bytes, trigger fraction, live edges, simulated time)."""
    runs = {}
    for tele in (None, Telemetry()):
        runs[tele is None] = _run(
            _with(ba_world, timing=HET, telemetry=tele),
            comm=CommConfig(codec="int8", trigger_threshold=0.3),
            backend=backend, layout=layout,
            schedule=Schedule(rounds=3, eval_every=3, deadline=4.0,
                              mode=mode))
    (on, _), (off, _) = runs[False], runs[True]
    assert _params_equal(on.params, off.params)
    assert _fingerprint(on) == _fingerprint(off)
    assert on.arrived_history == off.arrived_history


# --------------------------------------------------- 2. channel arithmetic

def test_channels_exact_always_fire(ring_world):
    """fp32 codec + zero threshold: every directed edge fires every round,
    so the per-edge and per-node counters are exact small integers and
    the byte channel reproduces the engine's own accounting."""
    rounds = 3
    exp, hist = _run(
        _with(ring_world, telemetry=Telemetry()),
        comm=CommConfig(codec="fp32", trigger_threshold=0.0),
        schedule=Schedule(rounds=rounds, eval_every=rounds))
    d = hist[-1].detail
    obs = exp.bound_obs
    e = obs.num_directed
    assert e == 8  # 4-ring: two directions per undirected edge
    np.testing.assert_array_equal(d["edge_trigger"], np.full(e, rounds))
    np.testing.assert_array_equal(d["edge_staleness"], np.zeros(e))
    np.testing.assert_array_equal(
        d["node_steps"], np.full(4, rounds * TINY["steps_per_round"]))
    assert float(np.sum(d["edge_bytes"])) == hist[-1].bytes_on_wire
    assert float(np.sum(d["edge_bytes"])) == exp.comm_bytes_total
    # drift is symmetric: ||w_src - w_dst|| == ||w_dst - w_src||
    pair = {(s, t): i for i, (s, t) in
            enumerate(zip(obs.edge_src, obs.edge_dst))}
    for (s, t), i in pair.items():
        assert d["drift"][i] == pytest.approx(d["drift"][pair[(t, s)]],
                                              rel=1e-6)
    # consensus matches a host-side recomputation from the final params
    from repro.utils.pytree import tree_flatten_stacked
    mat = np.asarray(tree_flatten_stacked(exp.params)[0])
    ref = np.linalg.norm(mat - mat.mean(axis=0, keepdims=True), axis=1)
    np.testing.assert_allclose(d["consensus"], ref, rtol=1e-5)


def test_staleness_counts_undelivered_rounds(ring_world):
    """A high threshold silences edges; the staleness channel ages them by
    one per silent round and resets to zero on delivery, so every age is
    bounded by the horizon."""
    rounds = 4
    _, hist = _run(
        _with(ring_world, telemetry=Telemetry()),
        comm=CommConfig(codec="int8", trigger_threshold=50.0),
        schedule=Schedule(rounds=rounds, eval_every=rounds))
    age = hist[-1].detail["edge_staleness"]
    assert np.all(age >= 0) and np.all(age <= rounds)
    assert np.any(age > 0)  # the threshold did silence something


# ------------------------------------------------------------- 3. parity

def test_detail_parity_across_engines(ba_world):
    """The materialized detail dict (canonical edge order) is identical
    across dense/sparse × vmap/shard_map and loop/fused."""
    combos = [("dense", "vmap", "fused"), ("sparse", "vmap", "fused"),
              ("dense", "shard_map", "fused"), ("sparse", "shard_map",
                                                "fused"),
              ("dense", "vmap", "loop")]
    ref = None
    for layout, backend, mode in combos:
        _, hist = _run(
            _with(ba_world, timing=HET, telemetry=Telemetry()),
            comm=CommConfig(codec="int8", trigger_threshold=0.3),
            backend=backend, layout=layout,
            schedule=Schedule(rounds=3, eval_every=3, deadline=4.0,
                              mode=mode))
        d = hist[-1].detail
        if ref is None:
            ref = d
        else:
            assert _detail_equal(ref, d), (layout, backend, mode)


def test_detail_parity_per_edge_transport(ring_world):
    """Same parity pin on the per-edge transport family."""
    ref = None
    for layout in ("dense", "sparse"):
        _, hist = _run(
            _with(ring_world, timing=HET, telemetry=Telemetry()),
            comm=CommConfig(codec="int8", trigger_threshold=0.3,
                            per_edge=True),
            layout=layout,
            schedule=Schedule(rounds=3, eval_every=3, deadline=4.0))
        d = hist[-1].detail
        if ref is None:
            ref = d
        else:
            assert _detail_equal(ref, d), layout


# ------------------------------------------------------------ 4. one-scan

def test_fused_program_is_one_scan_with_channels(ring_world):
    """ALL channels accumulate inside the scan carry: the K-round fused
    schedule still lowers to exactly ONE top-level lax.scan."""
    exp = Experiment(_with(ring_world, timing=HET, telemetry=Telemetry()),
                     "decdiff+vt",
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


# ----------------------------------------------------- 5. channel catalog

def test_auto_selects_supported_channels(ring_world):
    # full stack: everything
    exp = Experiment(_with(ring_world, timing=HET, telemetry=Telemetry()),
                     "decdiff+vt",
                     comm=CommConfig(codec="int8", trigger_threshold=0.3),
                     schedule=Schedule(rounds=1, eval_every=1), **TINY)
    assert exp.bound_obs.channels == tuple(CHANNELS)
    # no timing: compute/latency channels drop out
    exp2 = Experiment(_with(ring_world, telemetry=Telemetry()),
                      "decdiff+vt",
                      comm=CommConfig(codec="int8", trigger_threshold=0.3),
                      schedule=Schedule(rounds=1, eval_every=1), **TINY)
    assert "node_compute" not in exp2.bound_obs.channels
    assert "edge_latency" not in exp2.bound_obs.channels
    # no transport: every comm-needing channel drops out (drift stays —
    # pairwise divergence needs only the graph, not a transport)
    exp3 = Experiment(_with(ring_world, telemetry=Telemetry()), "decavg",
                      schedule=Schedule(rounds=1, eval_every=1), **TINY)
    assert "drift" in exp3.bound_obs.channels
    assert not any("comm" in CHANNELS[c].needs
                   for c in exp3.bound_obs.channels)


def test_channel_validation_errors(ring_world):
    # unknown channel name
    with pytest.raises(ValueError, match="unknown telemetry channel"):
        Telemetry(channels=("nope",))
    with pytest.raises(ValueError, match="alias"):
        Telemetry(channels="everything")
    # explicit channel whose subsystem is missing names the subsystem
    with pytest.raises(ValueError, match="timing"):
        Experiment(_with(ring_world,
                         telemetry=Telemetry(channels=("node_compute",))),
                   "decdiff+vt",
                   comm=CommConfig(codec="int8", trigger_threshold=0.3),
                   schedule=Schedule(rounds=1, eval_every=1), **TINY)
    # non-Telemetry value is a TypeError at construction
    with pytest.raises(TypeError, match="Telemetry"):
        Experiment(_with(ring_world, telemetry=object()), "decavg",
                   schedule=Schedule(rounds=1, eval_every=1), **TINY)


def test_catalog_helpers():
    assert available_channels() == tuple(CHANNELS)
    picked = channels_for(["drift", "node_steps"])
    assert set(picked) == {"drift", "node_steps"}
    with pytest.raises(ValueError, match="unknown telemetry channel"):
        channels_for(["nope"])
    for spec in CHANNELS.values():
        assert spec.axis in ("node", "edge")
        assert spec.doc


# ------------------------------------------------------- 6. ledger schema

def test_ledger_round_trip(ring_world, tmp_path):
    path = tmp_path / "run.jsonl"
    exp, hist = _run(
        _with(ring_world, timing=HET,
              telemetry=Telemetry(ledger=str(path))),
        comm=CommConfig(codec="int8", trigger_threshold=0.3),
        schedule=Schedule(rounds=4, eval_every=2, deadline=4.0,
                          mode="fused"))
    counts = validate_ledger(str(path))
    assert counts["manifest"] == 1
    assert counts["round"] == len(hist)
    assert counts["summary"] == 1
    manifest, rounds, summaries = read_ledger(str(path))
    assert manifest["nodes"] == 4
    assert manifest["method"] == "decdiff+vt"
    assert manifest["channels"] == list(exp.bound_obs.channels)
    assert manifest["payload_bytes"] == exp.transport.payload_bytes
    assert manifest["env"]["jax"]  # env block is present and non-empty
    for rec, m in zip(rounds, hist):
        assert rec["round"] == m.round
        assert rec["acc_mean"] == pytest.approx(m.acc_mean)
        assert rec["bytes_on_wire"] == m.bytes_on_wire
        got = np.asarray(rec["detail"]["edge_bytes"])
        np.testing.assert_allclose(got, m.detail["edge_bytes"])
    [summary] = summaries
    assert summary["rounds"] == 4
    assert summary["wall_s"] > 0
    assert summary["rounds_per_sec"] > 0
    assert "compile_s" in summary  # fresh experiment: cold compile
    # the fresh program's one request, a compile unless the persistent
    # cache served it
    assert summary["compile_requests"] >= 1
    assert summary["cold_compile"] == (summary["compile_requests"]
                                       > summary["cache_hits"])
    assert summary["compile_s"] == pytest.approx(summary["lower_s"]
                                                 + summary["load_s"])
    assert summary["lower_s"] > 0
    # seconds spent reading the persistent cache: none without a hit
    assert summary["cache_retrieval_s"] >= 0
    if summary["cache_hits"] == 0:
        assert summary["cache_retrieval_s"] == 0


def test_validate_record_rejects_garbage():
    with pytest.raises(ValueError, match="kind"):
        validate_record({"no": "kind"})
    with pytest.raises(ValueError, match="unknown ledger record kind"):
        validate_record({"kind": "banana"})
    with pytest.raises(ValueError, match="round"):
        validate_record({"kind": "round", "acc_mean": 0.5})
    with pytest.raises(ValueError, match="acc_mean"):
        validate_record({"kind": "round", "round": 1, "acc_mean": "high",
                         "acc_std": 0.0, "loss_mean": 1.0,
                         "acc_per_node": [0.5]})


def test_ledger_requires_manifest_first(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text(json.dumps({"kind": "round", "round": 0,
                                "acc_mean": 0.1, "acc_std": 0.0,
                                "loss_mean": 1.0,
                                "acc_per_node": [0.1]}) + "\n")
    with pytest.raises(ValueError, match="manifest"):
        validate_ledger(str(path))


def test_verbose_line_is_byte_stable(ring_world, capsys):
    """The structured logger emits EXACTLY the pre-ledger `_print_round`
    text on stdout, so scripts that scrape verbose output keep working."""
    exp = Experiment(_with(ring_world, timing=HET), "decdiff+vt",
                     comm=CommConfig(codec="int8", trigger_threshold=0.3),
                     schedule=Schedule(rounds=2, eval_every=2, deadline=4.0),
                     **TINY)
    hist = exp.run(verbose=True)
    out = capsys.readouterr().out.splitlines()
    expected = [format_round(exp.method.name, m) for m in hist]
    assert [l for l in out if l.startswith("[")] == expected
    # and the format itself is pinned against the historical layout
    m = RoundMetrics(round=7, acc_per_node=np.array([0.5, 0.7]),
                     loss_per_node=np.array([1.0, 2.0]),
                     bytes_on_wire=1024.0, triggered_frac=0.5)
    assert format_round("decdiff+vt", m) == (
        "[decdiff+vt] round    7  acc 0.6000 ± 0.1000  loss 1.5000  "
        "wire 0.00 MB  trig 0.50")


# -------------------------------------------------------- 7. trace export

def test_trace_export_bytes_exact(ring_world, tmp_path):
    exp, hist = _run(
        _with(ring_world, timing=HET, telemetry=Telemetry()),
        comm=CommConfig(codec="int8", trigger_threshold=0.3),
        schedule=Schedule(rounds=4, eval_every=4, deadline=4.0,
                          mode="fused"))
    path = tmp_path / "trace.json"
    trace = export_trace(exp, str(path))
    loaded = json.loads(path.read_text())
    assert loaded == trace
    evs = loaded["traceEvents"]
    spans = [e for e in evs if e.get("ph") == "X"]
    node_spans = [e for e in spans if e["pid"] == 0]
    edge_spans = [e for e in spans if e["pid"] == 1]
    assert len(node_spans) == 4 * 4  # nodes × rounds
    # every transfer span carries exact bytes; their total IS the wire total
    total = sum(e["args"]["bytes"] for e in edge_spans)
    assert total == hist[-1].bytes_on_wire
    # deadline mode: spans annotate arrival vs deadline
    assert all("deadline_s" in e["args"] for e in edge_spans)
    assert all(e["args"]["src"] != e["args"]["dst"] for e in edge_spans)
    # timestamps are microseconds within the simulated horizon
    horizon_us = hist[-1].sim_time * 1e6
    assert all(0 <= e["ts"] <= horizon_us for e in spans)


def test_trace_requires_timing_and_telemetry(ring_world):
    exp, _ = _run(_with(ring_world, timing=HET),
                  comm=CommConfig(codec="int8", trigger_threshold=0.3),
                  schedule=Schedule(rounds=1, eval_every=1, deadline=4.0))
    with pytest.raises(ValueError, match="telemetry"):
        build_trace(exp)
    exp2, _ = _run(_with(ring_world, telemetry=Telemetry()),
                   comm=CommConfig(codec="int8", trigger_threshold=0.3),
                   schedule=Schedule(rounds=1, eval_every=1))
    with pytest.raises(ValueError, match="timing"):
        build_trace(exp2)


# --------------------------------------------- 7. performance spans

def _hlo_scopes(exp, rounds=2):
    """{innermost dfl.* scope} over the op_name metadata of the compiled
    fused program."""
    hlo = exp.compile(rounds, rounds).as_text()
    found = set()
    for meta in re.findall(r'op_name="([^"]*)"', hlo):
        inner = [c for c in meta.split("/") if c in SCOPES]
        if inner:
            found.add(inner[-1])
    return found


@pytest.mark.parametrize("layout, comm, want", [
    # the paper's path: per-node fp32 gossip, dense, vmap, DecDiff+VT
    ("dense", CommConfig(codec="fp32"), set(SCOPES)),
    ("sparse", CommConfig(codec="fp32"), {"dfl.reduce", "dfl.exchange"}),
    ("dense", CommConfig(codec="int8", per_edge=True),
     {"dfl.reduce", "dfl.exchange"}),
    ("sparse", CommConfig(codec="int8", per_edge=True),
     {"dfl.reduce", "dfl.exchange"}),
])
def test_round_phases_are_named_in_the_compiled_program(ring_world, layout,
                                                        comm, want):
    exp = Experiment(ring_world, "decdiff+vt", comm=comm, layout=layout,
                     schedule=Schedule(rounds=2, eval_every=2), **TINY)
    assert want <= _hlo_scopes(exp)


def test_compile_counts_one_request_and_a_warm_run_none(ring_world):
    exp = Experiment(ring_world, "decdiff+vt", comm=CommConfig(codec="fp32"),
                     schedule=Schedule(rounds=2, eval_every=2), **TINY)
    exp.compile(2, 2)
    assert exp.compile_stats["lower_s"] > 0
    assert exp.compile_stats["load_s"] > 0
    assert exp.compile_stats["compile_requests"] == 1
    before = span_table()
    for _ in range(2):
        moved = counters()
        exp.run(rounds=2, eval_every=2)
        moved = counter_diff(counters(), moved)
        assert moved["compile_requests"] == 0
        assert moved["lower_s"] == 0
    after = span_table()
    for name in ("dfl.run", "dfl.run.dispatch", "dfl.run.fetch",
                 "dfl.run.account"):
        assert after[name]["count"] - before.get(
            name, {"count": 0})["count"] == 2
    assert after["dfl.compile.lower"]["lower_s"] > 0
    assert after["dfl.compile.load"]["compile_requests"] >= 1


def test_span_table_keeps_the_compiles_inside_each_span():
    before = counters()
    outer0 = span_table().get("test.outer", {"count": 0,
                                             "compile_requests": 0})
    with span("test.outer"):
        with span("test.inner"):
            # a function JAX has never seen compiles once
            jax.jit(lambda x: x * 3.0 + 1.0)(np.float32(2.0))
    moved = counter_diff(counters(), before)
    assert moved["compile_requests"] == 1
    assert moved["lower_s"] > 0
    table = span_table()
    assert table["test.outer"]["count"] == outer0["count"] + 1
    assert (table["test.outer"]["compile_requests"]
            - outer0["compile_requests"]) == 1
    assert table["test.inner"]["compile_requests"] >= 1
    assert table["test.outer"]["seconds"] >= table["test.inner"]["seconds"]


def test_nested_lowering_counts_once():
    """A jitted callee is traced inside its caller's trace: its seconds
    are already in the caller's and count once."""
    import time

    @jax.jit
    def inner(x):
        time.sleep(0.5)  # runs while tracing
        return x * 2.0

    @jax.jit
    def outer(x):
        return inner(x) + 1.0

    before = counters()
    t0 = time.perf_counter()
    outer.lower(np.float32(1.0))
    wall = time.perf_counter() - t0
    lowered = counter_diff(counters(), before)["lower_s"]
    assert 0.5 <= lowered <= wall


def test_profile_without_channels_binds_no_telemetry_state(ring_world,
                                                           tmp_path):
    """`Telemetry(channels=(), profile_dir=...)` profiles the program that
    runs untelemetered: no channel state in the carry, the same params."""
    runs = {}
    for tele in (None, Telemetry(channels=(), profile_dir=str(tmp_path))):
        exp, _ = _run(_with(ring_world, telemetry=tele),
                      comm=CommConfig(codec="fp32"),
                      schedule=Schedule(rounds=2, eval_every=2))
        runs[tele is None] = exp
    prof, plain = runs[False], runs[True]
    assert prof.bound_obs is None and prof.obs_state is None
    assert len(prof._get_states()) == len(plain._get_states())
    assert _params_equal(prof.params, plain.params)
    assert glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)


# ------------------------------------------------- 8. metrics edge cases

def _metric(round_, acc):
    return RoundMetrics(round=round_, acc_per_node=np.array([acc]),
                        loss_per_node=np.array([1.0]))


def test_characteristic_time_edge_cases():
    hist = [_metric(0, 0.1), _metric(5, 0.5), _metric(9, 0.52)]
    out = characteristic_time(hist, centralized_acc=0.6)
    assert out[0.5] == 5       # first round reaching 0.3
    assert out[0.95] is None   # never reaches 0.57
    with pytest.raises(ValueError, match="empty history"):
        characteristic_time([], centralized_acc=0.6)
    with pytest.raises(ValueError, match="centralized_acc"):
        characteristic_time(hist, centralized_acc=0.0)
    with pytest.raises(ValueError, match="centralized_acc"):
        characteristic_time(hist, centralized_acc=-1.0)


def test_accuracy_table_rejects_empty_history():
    with pytest.raises(ValueError, match="decavg"):
        accuracy_table({"decavg": []})
    table = accuracy_table({"isol": [_metric(3, 0.4)]})
    assert table["isol"]["round"] == 3
    assert table["isol"]["acc_mean"] == pytest.approx(0.4)
