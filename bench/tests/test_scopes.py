"""The scope and compile-counter metrics: the innermost-scope rule, the
event-metadata reader on two traces recorded on a TPU v5e, each new metric
on a hand-made reduced trace, and the window's compile count from a profile
captured on the CPU.

scoped/mlp-2rounds.xplane.pb is the `mlp.gossip-fp32` program at the
paper's widths (its world and weights) compiled for calls of 2 rounds with
both rounds' evals, one call profiled inside `bench.window` on a v5e."""
import json
import os

import jax
import numpy as np
import pytest

from bench import scopes
from bench import trace as tr
from bench.run import HERE, load_module

FIXTURE = os.path.join(os.path.dirname(__file__), "data")
SCOPED = os.path.join(os.path.dirname(__file__), "scoped")
SHARES = {"round.train.busy_share": "dfl.train",
          "round.exchange.busy_share": "dfl.exchange",
          "round.reduce.busy_share": "dfl.reduce",
          "round.aggregate.busy_share": "dfl.aggregate",
          "eval.busy_share": "dfl.eval"}
OLD = ("idle_share", "mfu", "segment_avg.roofline",
       "segment_avg.busy_share", "setup.init_s", "setup.compile_s")
NEW = tuple(SHARES) + ("round.reduce.roofline", "setup.lower_s",
                       "setup.load_s", "window.compiles")


def _metric(name):
    return load_module(os.path.join(HERE, "metrics", f"{name}.py"),
                       f"bench_metric_{name}")


@pytest.mark.parametrize("op_name, scope", [
    ("jit(program)/while/body/closed_call/dfl.aggregate/dfl.reduce/"
     "jit(segment_neighbor_avg)/jit(_pad)/pad:", "dfl.reduce"),
    ("jit(program)/while/body/dfl.aggregate/reshape", "dfl.aggregate"),
    ("jit(program)/while/body/dfl.train/transpose(jvp(dfl.reduce))/mul",
     "dfl.reduce"),
    ("jit(program)/while/body/cond/branch_1_fun/dfl.eval", "dfl.eval"),
    ("jit(program)/while/body/closed_call/jit(_threefry_split)/slice",
     "other"),
    ("jit(f)/dfl.reduced/add", "other"),
    ("jit(f)/my.dfl.train/add", "other"),
    ("", "other"),
    (None, "other"),
])
def test_the_innermost_scope_wins(op_name, scope):
    assert scopes.scope_of(op_name) == scope
    assert scopes.op_scopes({"fusion.1": (op_name, ())}) == {
        "fusion.1": scope}


def test_an_op_without_a_scope_takes_its_consumers_one():
    """A compiler-made op (op_name dropped, or the enclosing loop's) is
    attributed to the one scope its consumers share; consumers that
    disagree, or none, leave it `other`."""
    loop = "jit(program)/while"
    ops = {
        # a dynamic-update-slice chain building the reduce's panel
        "dus.1": (None, ("param.0",)),
        "dus.2": (loop, ("dus.1", "bitcast.3")),
        "pad.4": (loop + "/body/dfl.aggregate/dfl.reduce/pad", ("dus.2",)),
        # read by two phases
        "copy.5": (loop, ("param.0",)),
        "fusion.6": (loop + "/body/dfl.train/mul", ("copy.5",)),
        "fusion.7": (loop + "/body/dfl.eval/add", ("copy.5",)),
        # no consumer among the ops
        "slice.8": (loop + "/body/jit(_threefry_split)/slice", ()),
        # a consumer that stays `other`
        "copy.9": (loop, ("slice.8",)),
        "convert.10": (loop, ("copy.9", "copy.5")),
    }
    got = scopes.op_scopes(ops)
    assert got["dus.1"] == got["dus.2"] == got["pad.4"] == "dfl.reduce"
    assert got["copy.5"] == "other"
    assert got["slice.8"] == got["copy.9"] == got["convert.10"] == "other"
    assert got["fusion.6"] == "dfl.train" and got["fusion.7"] == "dfl.eval"


def test_op_metadata_of_a_recorded_tpu_trace():
    """Every op the reduction times has an entry; the kernel's op_name is
    the one its program gave it, with no `dfl.*` scope in it."""
    path = tr.newest_xplane(FIXTURE)
    meta = scopes.op_metadata(path)
    assert set(tr.reduce(tr.load(FIXTURE))["op_s"]) <= set(meta)
    assert meta["segment_avg_chunk.5"][0] == (
        "jit(<lambda>)/jit(segment_neighbor_avg)/while/body/closed_call/"
        "jit(segment_avg_chunk)/pallas_call:")
    # the HLO text names the operands: the kernel reads the padded chunk
    assert any(ref.startswith("dynamic-slice_bitcast_fusion")
               for ref in meta["segment_avg_chunk.5"][1])
    assert set(scopes.op_scopes(meta).values()) == {"other"}
    lo, hi, length = scopes.window_wall(path)
    assert hi - lo == pytest.approx(length, abs=1e-6)
    assert length == pytest.approx(tr.reduce(tr.load(FIXTURE))["window_s"])


def test_scopes_of_a_recorded_scoped_trace(monkeypatch):
    """The protobuf walk finds all five scopes in the `tf_op` stats; the
    chain of 18 dynamic-update-slice fusions that builds the reduce's
    gathered [50,16,D] panel has no scope of its own and takes `dfl.reduce`
    from its consumers (the six that build the flattened params take
    `dfl.aggregate`); the share metrics, reading the profile through the
    run's own window, account for every busy second with `other`."""
    meta = scopes.op_metadata(tr.newest_xplane(SCOPED))
    by_op = scopes.op_scopes(meta)
    own = {op: scopes.scope_of(m) for op, (m, _) in meta.items()}
    assert set(SHARES.values()) <= set(own.values())
    assert by_op["segment_avg_chunk.5"] == own["segment_avg_chunk.5"] \
        == "dfl.reduce"
    chain = {op: by_op[op] for op in meta
             if op.startswith("constant_dynamic-update-slice_fusion")}
    assert all(own[op] == "other" for op in chain)
    assert sorted(chain.values()) == ["dfl.aggregate"] * 6 + \
        ["dfl.reduce"] * 18

    monkeypatch.setattr(scopes, "PROFILES", SCOPED)
    ctx = {"trace": tr.reduce(tr.load(SCOPED))}
    shares = {name: _metric(name).read(ctx) for name in SHARES}
    assert ctx["scopes"] == by_op
    secs = scopes.scope_seconds(ctx)
    assert sum(secs.values()) == pytest.approx(ctx["trace"]["busy_s"],
                                               rel=1e-9)
    assert all(v > 0 for v in shares.values())
    assert sum(shares.values()) == pytest.approx(
        100.0 * (1 - secs["other"] / ctx["trace"]["busy_s"]))
    assert sum(shares.values()) >= 95.0
    chain_s = sum(ctx["trace"]["op_s"].get(op, 0.0) for op, sc
                  in chain.items() if sc == "dfl.reduce")
    assert chain_s > 0.05 * ctx["trace"]["busy_s"]


def _ctx():
    """A hand-made reduced trace of one traced round: 10 ms busy in a
    10.5 ms window."""
    peaks = json.load(open(os.path.join(HERE, "peaks.json")))["devices"]
    op_s = {"fusion.1": 0.002, "fusion.2": 0.0005, "gather.3": 0.001,
            "segment_avg_chunk.4": 0.003, "copy.5": 0.001,
            "fusion.6": 0.0015, "while.7": 0.001}
    by_op = {"fusion.1": "dfl.train", "fusion.2": "dfl.exchange",
             "gather.3": "dfl.reduce", "segment_avg_chunk.4": "dfl.reduce",
             "copy.5": "dfl.aggregate", "fusion.6": "dfl.eval",
             "while.7": "other"}
    return {
        "trace": {"window_s": 0.0105, "busy_s": 0.010, "op_s": op_s},
        "scopes": by_op,
        "program": {"lower_s": 9.5, "load_s": 10.25, "window_compiles": 0},
        "peaks": peaks["TPU v5 lite"], "chips": 1,
        "directed_edges": 504, "params_per_node": 567_434, "nodes": 50,
        "traffic": {"wire_bytes_per_value": 4},
        "calls_traced": 1, "rounds_traced": 1,
        "kernels": {"segment_avg_chunk": 1}, "flops_per_call": 1e9,
        "setup": {"init_s": 5.0, "compile_s": 20.0},
    }


def test_new_metrics_on_a_hand_made_trace():
    ctx = _ctx()
    got = {name: _metric(name).read(ctx) for name in NEW}
    assert got["round.train.busy_share"] == pytest.approx(20.0)
    assert got["round.exchange.busy_share"] == pytest.approx(5.0)
    assert got["round.reduce.busy_share"] == pytest.approx(40.0)
    assert got["round.aggregate.busy_share"] == pytest.approx(10.0)
    assert got["eval.busy_share"] == pytest.approx(15.0)
    assert sum(got[m] for m in SHARES) == pytest.approx(90.0)
    least = _metric("segment_avg.roofline").least_seconds(ctx)
    assert got["round.reduce.roofline"] == pytest.approx(
        100.0 * least / 0.004)
    # the scope join and the kernel-name join agree on least over busy
    kernel = (_metric("segment_avg.roofline").read(ctx)
              * _metric("segment_avg.busy_share").read(ctx))
    assert (got["round.reduce.roofline"] * got["round.reduce.busy_share"]
            == pytest.approx(kernel))
    assert got["setup.lower_s"] == 9.5 and got["setup.load_s"] == 10.25
    assert got["window.compiles"] == 0


@pytest.mark.parametrize("by_op", [
    None,                                      # no profile of this run
    {"fusion.1": "other", "segment_avg_chunk.4": "other"},  # no scopes
])
def test_new_metrics_read_nothing_on_a_program_without_them(by_op):
    ctx = _ctx()
    ctx["scopes"] = by_op
    ctx["program"] = None
    assert all(_metric(name).read(ctx) is None for name in NEW)


@pytest.mark.parametrize("fixture", ["hand-made", "tiny.xplane.pb"])
def test_old_metrics_read_what_they_read_before(fixture):
    """The new readers fill `ctx` lazily; the six metrics of the accepted
    benchmark read the same numbers with and without them."""
    ctx = _ctx()
    if fixture != "hand-made":
        ctx["trace"] = tr.reduce(tr.load(FIXTURE))
    before = {name: _metric(name).read(ctx) for name in OLD}
    for name in NEW:
        _metric(name).read(ctx)
    assert {name: _metric(name).read(ctx) for name in OLD} == before
    if fixture == "hand-made":
        assert before["idle_share"] == pytest.approx(100 / 21)
        assert before["segment_avg.busy_share"] == pytest.approx(30.0)
        assert before["setup.compile_s"] == 20.0


def _compile_spans():
    """compile()'s two spans, as the program opens them."""
    from repro.obs import spans

    f = jax.jit(lambda x: x * 5.0 - 1.0)
    with spans.span("dfl.compile.lower"):
        lowered = f.lower(np.float32(1.0))
    with spans.span("dfl.compile.load"):
        lowered.compile()


def _window(path, body):
    with jax.profiler.trace(path):
        with jax.profiler.TraceAnnotation("bench.window"):
            body()
    return {"trace": tr.reduce(tr.load(path))}


def test_program_counters_and_the_window_compiles(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "PROFILES", str(tmp_path))
    _compile_spans()
    seen = jax.jit(lambda x: x + 2.0)
    seen(np.float32(0.0))
    quiet = _window(str(tmp_path / "quiet"),
                    lambda: seen(np.float32(1.0)).block_until_ready())
    p = scopes.program(quiet)
    assert p["lower_s"] > 0 and p["load_s"] > 0
    assert p["window_compiles"] == 0
    assert quiet["scopes"] == {}  # a CPU trace has no accelerator ops
    fresh = jax.jit(lambda x: x - 7.0)
    loud = _window(str(tmp_path / "loud"),
                   lambda: fresh(np.float32(1.0)).block_until_ready())
    assert scopes.program(loud)["window_compiles"] == 1
    # the newest profile is not this run's: nothing is read
    assert scopes.program({"trace": quiet["trace"]})["window_compiles"] \
        is None
