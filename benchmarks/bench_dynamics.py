"""Dynamics suite: accuracy/bytes under time-varying topologies.

The ISSUE-5 tentpole measurement: DecDiff+VT on the 16-node BA and ER smoke
worlds, dense fp32 vs the production int8+adaptive transport, under every
catalog `repro.dynamics.GraphProcess` vs the static baseline:

  * ``static``           — the frozen graph (the per-(world, comm) baseline
    every dynamic point is scored against),
  * ``dropout(p=0.2)``   — i.i.d. edge failures (the acceptance process),
  * ``gilbert_elliott``  — bursty links (0.1, 0.3): same 0.75 stationary
    up-rate as dropout p=0.25 but with multi-round outages,
  * ``churn``            — device churn (0.05, 0.5): ~91% stationary
    aliveness with full per-edge comm-state resets on rejoin,
  * ``rewire``           — periodic Watts–Strogatz re-draws (period 5, 4
    graphs) over the union layout.

Each point reports final accuracy, exact bytes on wire (live edges only —
a non-existent link costs nothing), the realized live-edge fraction and the
triggered fraction.  `gen_report.write_bench_dynamics()` folds the sweep
into BENCH_dynamics.json with the acceptance gate: int8+adaptive under
i.i.d. dropout (p=0.2) stays within 3% (relative) of its own static-graph
final accuracy on the 16-node BA world.

    PYTHONPATH=src python -m benchmarks.bench_dynamics [--rounds 40]
    PYTHONPATH=src python -m benchmarks.bench_dynamics --smoke   # CI lane
"""
from __future__ import annotations

import argparse

from benchmarks.common import save_results
from repro.comm import CommConfig
from repro.dynamics import (
    EdgeDropout,
    GilbertElliott,
    NodeChurn,
    PeriodicRewiring,
    StaticGraph,
)
from repro.engine import Experiment, Schedule, World
from repro.utils.compile_cache import enable_compile_cache

ROUNDS = 40
EVAL_EVERY = 5

# (label, process factory) — factories so every run binds a fresh instance
PROCESSES = [
    ("static", lambda: StaticGraph()),
    ("dropout(p=0.2)", lambda: EdgeDropout(p=0.2)),
    ("gilbert_elliott(0.1,0.3)", lambda: GilbertElliott(p_gb=0.1, p_bg=0.3)),
    ("churn(0.05,0.5)", lambda: NodeChurn(p_leave=0.05, p_rejoin=0.5)),
    ("rewire(ws,T=5,K=4)", lambda: PeriodicRewiring(period=5, num_graphs=4)),
]
COMMS = [
    ("dense-fp32", dict(codec="fp32")),
    ("int8+adaptive", dict(codec="int8", policy="adaptive",
                           target_trigger=0.95)),
]
WORLDS = [("ba", dict(topology="barabasi_albert", m=2)),
          ("er", dict(topology="erdos_renyi", p=0.3))]


def make_world(graph_kwargs, nodes=16, seed=0, dynamics=None):
    """The 16-node smoke worlds (bench_engine's scaled comm smoke config)."""
    from repro.models.mlp_cnn import make_mlp

    return World.synthetic(dataset="synth-mnist", nodes=nodes, seed=seed,
                           scale=0.03,
                           model=make_mlp(num_classes=10, hidden=(64, 32)),
                           dynamics=dynamics, **graph_kwargs)


def run(rounds=ROUNDS, nodes=16, seed=0, worlds=None, verbose=True,
        smoke=False):
    rows = []
    for wname, wkw in (worlds or WORLDS):
        for cname, ckw in COMMS:
            for pname, factory in PROCESSES:
                world = make_world(wkw, nodes=nodes, seed=seed,
                                   dynamics=factory())
                exp = Experiment(
                    world, "decdiff+vt", comm=CommConfig(**ckw),
                    schedule=Schedule(rounds=rounds, eval_every=EVAL_EVERY),
                    steps_per_round=4, batch_size=32, lr=0.1, momentum=0.9,
                    seed=seed)
                hist = exp.run()
                last = hist[-1]
                rows.append({
                    "world": wname, "process": pname, "comm": cname,
                    "nodes": nodes, "rounds": rounds, "seed": seed,
                    "acc_mean": last.acc_mean, "acc_std": last.acc_std,
                    "bytes_on_wire": exp.comm_bytes_total,
                    "payload_bytes": exp.transport.payload_bytes,
                    "triggered_frac": last.triggered_frac,
                    "live_edge_frac": last.live_edge_frac,
                })
                if verbose:
                    r = rows[-1]
                    print(f"[{wname}] {cname:>13} {pname:<24} "
                          f"acc={r['acc_mean']:.4f} "
                          f"wire={r['bytes_on_wire'] / 1e6:7.2f} MB "
                          f"live={r['live_edge_frac']:.2f} "
                          f"trig={r['triggered_frac']:.2f}", flush=True)
    # score every point against its own (world, comm) static baseline
    for r in rows:
        base = next(b for b in rows
                    if b["world"] == r["world"] and b["comm"] == r["comm"]
                    and b["process"] == "static")
        r["acc_delta_vs_static"] = r["acc_mean"] - base["acc_mean"]
        r["bytes_ratio_vs_static"] = (r["bytes_on_wire"]
                                      / max(base["bytes_on_wire"], 1))
    if smoke:
        save_results("dynamics_smoke", rows)
        return rows
    save_results("dynamics_suite", rows)
    from benchmarks.gen_report import write_bench_dynamics

    path = write_bench_dynamics()
    if verbose and path:
        print("wrote", path)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=ROUNDS)
    ap.add_argument("--nodes", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI lane: 8 nodes x 5 rounds on the BA world "
                         "only; writes the dynamics_smoke artifact and does "
                         "NOT touch BENCH_dynamics.json")
    args = ap.parse_args()
    if args.smoke:
        rows = run(rounds=5, nodes=8, seed=args.seed,
                   worlds=[WORLDS[0]], smoke=True)
        assert all(r["acc_mean"] == r["acc_mean"] for r in rows)  # finite
        print(f"smoke ok: {len(rows)} (process x comm) points")
    else:
        run(rounds=args.rounds, nodes=args.nodes, seed=args.seed)


if __name__ == "__main__":
    enable_compile_cache()
    main()
