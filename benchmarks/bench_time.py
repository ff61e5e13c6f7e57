"""Time-to-accuracy suite: the event clock prices rounds in simulated
seconds, so methods are compared on WHEN they reach a target accuracy, not
in how many rounds (ISSUE-9 tentpole measurement).

On the 16-node BA and ER smoke worlds under heterogeneous per-node compute
(lognormal step times, sigma 0.5) and heterogeneous links (lognormal
latency/bandwidth priced from the codec's EXACT bytes on wire):

  * ``sync-fp32``       — the dense baseline: fp32 always-send gossip on a
    synchronous schedule; every round waits for the slowest node AND the
    slowest link (the clock reports the realized makespan),
  * ``deadline-int8``   — the production challenger: per-edge adaptive int8
    event-triggered transport under `Schedule(deadline=...)`; stragglers
    train what fits in the tick, late payloads fall into the stale path,
    and the int8 payload is ~4x cheaper on the same links.

The frontier metric is `time_to_target`: the first evaluated sim_time at
which node-mean accuracy reaches 90% of the sync baseline's OWN final
accuracy on that world.  Acceptance (folded into BENCH_time.json by
`gen_report.write_bench_time()`): the challenger reaches the target in
STRICTLY less simulated time on both worlds.

The straggler scenario reruns the challenger with 10% of nodes 8x slower
(`StragglerStep`) vs the homogeneous clock, same deadline: final accuracy
must stay within 3% (relative) — the deadline tick absorbs stragglers
instead of stalling the whole graph on them.

    PYTHONPATH=src python -m benchmarks.bench_time [--rounds 40]
    PYTHONPATH=src python -m benchmarks.bench_time --smoke   # CI lane
"""
from __future__ import annotations

import argparse

from benchmarks.common import save_results
from repro.comm import CommConfig
from repro.engine import Experiment, Schedule, World
from repro.timing import (
    ConstantStep,
    LognormalLink,
    LognormalStep,
    StragglerStep,
    Timing,
)
from repro.utils.compile_cache import enable_compile_cache

ROUNDS = 40
EVAL_EVERY = 5
DEADLINE = 6.0          # simulated seconds per deadline tick
TARGET_FRAC = 0.90      # of the sync baseline's own final accuracy

# lognormal links: ~50 ms latency, ~100 KB/s bandwidth — the ~210 KB fp32
# MLP payload costs ~2 s/edge, the int8 payload ~4x less, so the codec
# choice moves the clock, not just the byte counter.
LINK = dict(latency_median=0.05, latency_sigma=0.5,
            bandwidth_median=1e5, bandwidth_sigma=0.5, seed=11)

WORLDS = [("ba", dict(topology="barabasi_albert", m=2)),
          ("er", dict(topology="erdos_renyi", p=0.3))]

CONFIGS = [
    # (label, comm kwargs, deadline or None for synchronous)
    ("sync-fp32", dict(codec="fp32"), None),
    ("deadline-int8", dict(codec="int8", policy="adaptive",
                           target_trigger=0.95, per_edge=True), DEADLINE),
]


def make_world(graph_kwargs, timing, nodes=16, seed=0):
    """The 16-node smoke worlds (bench_dynamics' config) + an event clock."""
    from repro.models.mlp_cnn import make_mlp

    return World.synthetic(dataset="synth-mnist", nodes=nodes, seed=seed,
                           scale=0.03,
                           model=make_mlp(num_classes=10, hidden=(64, 32)),
                           timing=timing, **graph_kwargs)


def _time_to(history, target_acc):
    """First evaluated sim_time with node-mean accuracy >= target."""
    for m in history:
        if m.acc_mean >= target_acc:
            return m.sim_time
    return None


def _run_one(wkw, timing, ckw, deadline, rounds, nodes, seed):
    world = make_world(wkw, timing, nodes=nodes, seed=seed)
    exp = Experiment(
        world, "decdiff+vt", comm=CommConfig(**ckw),
        schedule=Schedule(rounds=rounds, eval_every=EVAL_EVERY,
                          deadline=deadline),
        steps_per_round=4, batch_size=32, lr=0.1, momentum=0.9, seed=seed)
    hist = exp.run()
    return exp, hist


def run(rounds=ROUNDS, nodes=16, seed=0, worlds=None, verbose=True,
        smoke=False, deadline=DEADLINE):
    het = Timing(node=LognormalStep(median=1.0, sigma=0.5, seed=7),
                 link=LognormalLink(**LINK))
    rows = []
    for wname, wkw in (worlds or WORLDS):
        for cname, ckw, dl in CONFIGS:
            if dl is not None:
                dl = deadline
            exp, hist = _run_one(wkw, het, ckw, dl, rounds, nodes, seed)
            last = hist[-1]
            rows.append({
                "world": wname, "config": cname, "scenario": "hetero",
                "nodes": nodes, "rounds": rounds, "seed": seed,
                "deadline": dl, "acc_mean": last.acc_mean,
                "sim_time": last.sim_time,
                "arrived_frac": last.arrived_frac,
                "triggered_frac": last.triggered_frac,
                "bytes_on_wire": exp.comm_bytes_total,
                "payload_bytes": exp.transport.payload_bytes,
                "history": [(m.sim_time, m.acc_mean) for m in hist],
            })
            if verbose:
                r = rows[-1]
                print(f"[{wname}] {cname:>14} acc={r['acc_mean']:.4f} "
                      f"t={r['sim_time']:8.1f}s "
                      f"arr={r['arrived_frac']:.2f} "
                      f"wire={r['bytes_on_wire'] / 1e6:6.2f} MB", flush=True)
    # frontier: challenger time-to-target vs the sync baseline, per world
    for wname, _ in (worlds or WORLDS):
        base = next(r for r in rows if r["world"] == wname
                    and r["config"] == "sync-fp32")
        target = TARGET_FRAC * base["acc_mean"]
        for r in rows:
            if r["world"] == wname:
                r["target_acc"] = target
                r["time_to_target"] = _time_to(
                    [type("M", (), {"acc_mean": a, "sim_time": t})()
                     for t, a in r["history"]], target)
    # straggler scenario: challenger clock with 10% of nodes 8x slower,
    # vs the homogeneous clock — same deadline, same links (BA world)
    cname, ckw, _ = CONFIGS[1]
    strag = {}
    for sname, node_model in [
            ("homogeneous", ConstantStep(dt=1.0)),
            ("straggler(0.1,8x)", StragglerStep(dt=1.0, frac=0.1,
                                                factor=8.0, seed=5))]:
        tm = Timing(node=node_model, link=LognormalLink(**LINK))
        exp, hist = _run_one(dict(WORLDS[0][1]), tm, ckw, deadline, rounds,
                             nodes, seed)
        last = hist[-1]
        strag[sname] = last.acc_mean
        rows.append({
            "world": "ba", "config": cname, "scenario": sname,
            "nodes": nodes, "rounds": rounds, "seed": seed,
            "deadline": deadline, "acc_mean": last.acc_mean,
            "sim_time": last.sim_time, "arrived_frac": last.arrived_frac,
            "triggered_frac": last.triggered_frac,
            "bytes_on_wire": exp.comm_bytes_total,
            "payload_bytes": exp.transport.payload_bytes,
            "history": [(m.sim_time, m.acc_mean) for m in hist],
        })
        if verbose:
            print(f"[ba] {sname:>17} acc={last.acc_mean:.4f} "
                  f"t={last.sim_time:8.1f}s", flush=True)
    for r in rows:
        if r["scenario"].startswith("straggler"):
            r["acc_delta_vs_homogeneous"] = (r["acc_mean"]
                                             - strag["homogeneous"])
    if smoke:
        save_results("time_smoke", rows)
        return rows
    save_results("time_suite", rows)
    from benchmarks.gen_report import write_bench_time

    path = write_bench_time()
    if verbose and path:
        print("wrote", path)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=ROUNDS)
    ap.add_argument("--nodes", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--deadline", type=float, default=DEADLINE)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI lane: 8 nodes x 5 rounds on the BA world "
                         "only; writes the time_smoke artifact and does NOT "
                         "touch BENCH_time.json")
    args = ap.parse_args()
    if args.smoke:
        rows = run(rounds=5, nodes=8, seed=args.seed, worlds=[WORLDS[0]],
                   smoke=True)
        assert all(r["acc_mean"] == r["acc_mean"] for r in rows)  # finite
        assert all(r["sim_time"] > 0 for r in rows)
        print(f"smoke ok: {len(rows)} (config x scenario) points")
    else:
        run(rounds=args.rounds, nodes=args.nodes, seed=args.seed,
            deadline=args.deadline)


if __name__ == "__main__":
    enable_compile_cache()
    main()
