"""Communication cost: exact codec accounting + the accuracy-vs-bytes frontier.

Two claims, two artifacts:

  * `comm_table` — paper §VI-A.3 per-method bytes/round (DecDiff+VT ships
    parameters only; CFA-GE 4x; FedAvg scales with |V|), now priced per
    codec with the *exact* serialized payload size from
    `codec.payload_bytes_for` instead of hard-coded fp32 math.
  * `comm_frontier` — the tentpole measurement: DecDiff+VT on seeded 8-node
    smoke worlds (Barabási–Albert scale-free AND Erdős–Rényi — hub-heavy
    vs degree-homogeneous, the two graph families the paper leans on),
    swept over codecs x trigger policies (fixed drift thresholds and the
    per-edge adaptive drift-rate controller) x top-k variants (ratios,
    momentum masking), each point reporting final accuracy, total bytes on
    wire (the simulator's dynamic accounting, so event-triggered silence
    is priced in), and the triggered fraction.  This turns "DecDiff trains
    accurate local models in a more communication-efficient way" into a
    measured frontier with two acceptance gates: the PR-2 >= 2x-within-1%
    gate, and the PR-3 gate that the adaptive per-edge policy reaches at
    least the within-1% byte reduction of the best fixed-threshold int8
    point.

`gen_report.write_bench_comm()` folds both into BENCH_comm.json.
"""
from __future__ import annotations

import jax

from benchmarks.common import save_results
from repro.comm import CommConfig, make_codec
from repro.data import make_dataset, zipf_allocation
from repro.data.allocation import split_by_allocation
from repro.engine import Experiment, Schedule, World
from repro.fl.metrics import comm_bytes_per_round
from repro.graphs import make_topology
from repro.models.mlp_cnn import make_cnn, make_mlp
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.pytree import tree_bytes, tree_size

METHODS = ["isol", "fedavg", "dechetero", "cfa", "cfa-ge", "decdiff", "decdiff+vt"]
CODECS = ["fp32", "bf16", "int8", "topk"]

# The seeded smoke sweeps: (codec, CommConfig overrides).  fp32/{} is the
# dense always-send reference every point in the SAME world is scored
# against.  The BA world carries the full sweep; the ER world re-runs the
# comparison subset (dense / fixed int8 / adaptive int8) so the adaptive
# policy is measured on both a hub-heavy and a degree-homogeneous graph.
FRONTIER = [
    ("fp32", {}),
    ("bf16", {}),
    ("int8", {}),
    ("int8", {"trigger_threshold": 0.5}),
    ("int8", {"trigger_threshold": 1.0}),
    ("int8", {"trigger_threshold": 2.5}),
    ("int8", {"policy": "adaptive", "target_trigger": 0.95}),
    ("int8", {"policy": "adaptive", "target_trigger": 0.9}),
    ("int8", {"policy": "adaptive", "target_trigger": 0.8}),
    ("int8", {"policy": "adaptive", "target_trigger": 0.5}),
    # top-k revisit: the PR-2 ratios underperformed (see ROADMAP); sweep
    # larger ratios and momentum masking on per-edge residuals.
    ("topk", {"topk_ratio": 0.05}),
    ("topk", {"topk_ratio": 0.01}),
    ("topk", {"topk_ratio": 0.1}),
    ("topk", {"topk_ratio": 0.25}),
    ("topk", {"topk_ratio": 0.1, "topk_momentum": 0.9, "per_edge": True}),
    ("topk", {"topk_ratio": 0.25, "topk_momentum": 0.9, "per_edge": True}),
]
ER_FRONTIER = [
    ("fp32", {}),
    ("int8", {}),
    ("int8", {"trigger_threshold": 1.0}),
    ("int8", {"policy": "adaptive", "target_trigger": 0.95}),
    ("int8", {"policy": "adaptive", "target_trigger": 0.8}),
]
WORLD_SWEEPS = (("ba", FRONTIER), ("er", ER_FRONTIER))


def static_table(verbose=True):
    """Per-method x per-codec bytes/round on the paper's 50-node ER(0.2)."""
    topo = make_topology("erdos_renyi", n=50, p=0.2, seed=0)
    models = {
        "mlp(mnist)": make_mlp(num_classes=10),
        "cnn(fashion)": make_cnn(num_classes=10),
        "cnn(emnist)": make_cnn(num_classes=26, use_pool_dropout=True),
    }
    rows = []
    for mname, model in models.items():
        params = model.init(jax.random.PRNGKey(0))
        n_params = tree_size(params)
        for codec_name in CODECS:
            # exact serialized payload size for one model transmission —
            # NOT n_params * 4 (int8 adds a scale word, top-k ships pairs)
            payload = make_codec(codec_name).payload_bytes_for(n_params)
            for method in METHODS:
                rows.append({
                    "model": mname, "params": n_params,
                    "model_mbytes": tree_bytes(params) / 1e6,
                    "codec": codec_name, "payload_bytes": payload,
                    "method": method,
                    "bytes_per_round": comm_bytes_per_round(method, topo, payload),
                })
    save_results("comm_table", rows)
    if verbose:
        print(format_table(rows))
    return rows


def smoke_world(seed=0, graph="ba"):
    """The seeded smoke configs shared with tests/test_system.py: an 8-node
    graph (graph="ba": Barabási–Albert scale-free, the default everything
    else pins; graph="er": Erdős–Rényi p=0.4), Zipf non-IID synth-mnist,
    small MLP."""
    ds = make_dataset("synth-mnist", seed=seed, scale=0.03)
    if graph == "ba":
        topo = make_topology("barabasi_albert", n=8, m=2, seed=1)
    elif graph == "er":
        topo = make_topology("erdos_renyi", n=8, p=0.4, seed=1)
    else:
        raise ValueError(f"unknown smoke graph {graph!r}")
    alloc = zipf_allocation(ds.y_train, 8, seed=1, min_per_class=1)
    xs, ys = split_by_allocation(ds.x_train, ds.y_train, alloc)
    model = make_mlp(num_classes=10, hidden=(64, 32))
    return ds, topo, xs, ys, model


def trigger_label(policy: str, threshold=0.0, target=None) -> str:
    """One rendering of a trigger config for every human-facing table (the
    bench log, gen_report's markdown, the example's terminal output)."""
    return (f"adaptive({target})" if policy == "adaptive"
            else f"thr={threshold}")


def _point_label(comm: CommConfig) -> str:
    if comm.codec == "topk" and comm.policy == "fixed":
        mom = f",mom={comm.topk_momentum}" if comm.topk_momentum > 0 else ""
        return f"r={comm.topk_ratio}{mom}"
    return trigger_label(comm.policy, comm.trigger_threshold,
                         comm.target_trigger)


def frontier(rounds=40, seed=0, verbose=True):
    """Sweep codecs x trigger policies on BA and ER worlds; emit the
    accuracy-vs-bytes frontier (per-world dense-normalized)."""
    rows = []
    for world, points in WORLD_SWEEPS:
        ds, topo, xs, ys, model = smoke_world(seed, graph=world)
        for codec, overrides in points:
            comm = CommConfig(codec=codec, **overrides)
            sim = Experiment(
                World(model=model, topo=topo, xs=xs, ys=ys,
                      x_test=ds.x_test, y_test=ds.y_test),
                "decdiff+vt", comm=comm,
                schedule=Schedule(rounds=rounds, eval_every=5),
                steps_per_round=4, batch_size=32, lr=0.1, momentum=0.9,
                seed=seed)
            hist = sim.run()
            rows.append({
                "world": world, "codec": codec, "policy": comm.policy,
                "per_edge": comm.use_per_edge,
                "threshold": comm.trigger_threshold,
                "target_trigger": (comm.target_trigger
                                   if comm.policy == "adaptive" else None),
                "topk_ratio": comm.topk_ratio if codec == "topk" else None,
                "topk_momentum": (comm.topk_momentum
                                  if codec == "topk" else None),
                "rounds": rounds, "seed": seed,
                "acc_mean": hist[-1].acc_mean, "acc_std": hist[-1].acc_std,
                "bytes_on_wire": sim.comm_bytes_total,
                "payload_bytes": sim.transport.payload_bytes,
                "triggered_frac": hist[-1].triggered_frac,
            })
            if verbose:
                r = rows[-1]
                print(f"[{world}] {codec:>5} {_point_label(comm):<16} "
                      f"acc={r['acc_mean']:.4f} "
                      f"wire={r['bytes_on_wire'] / 1e6:8.2f} MB "
                      f"trig={r['triggered_frac']:.2f}", flush=True)
    for world, _ in WORLD_SWEEPS:
        dense = next(r for r in rows if r["world"] == world
                     and r["codec"] == "fp32" and r["policy"] == "fixed"
                     and r["threshold"] == 0.0)
        for r in rows:
            if r["world"] != world:
                continue
            r["reduction_vs_dense"] = (dense["bytes_on_wire"]
                                       / max(r["bytes_on_wire"], 1))
            r["acc_delta_vs_dense"] = r["acc_mean"] - dense["acc_mean"]
    save_results("comm_frontier", rows)
    return rows


def format_table(rows) -> str:
    lines = ["| model | codec | method | MB/round (50-node ER p=.2) |",
             "|---|---|---|---|"]
    for r in rows:
        if r["method"] not in ("fedavg", "cfa-ge", "decdiff+vt"):
            continue
        lines.append(f"| {r['model']} | {r['codec']} | {r['method']} | "
                     f"{r['bytes_per_round'] / 1e6:.2f} |")
    return "\n".join(lines)


def run(verbose=True, rounds=40, with_frontier=True):
    """Returns the static-table rows (benchmarks/run.py's contract); the
    frontier sweep (~10 min of simulator runs) is skippable for callers that
    only need the accounting table."""
    rows = static_table(verbose=verbose)
    if with_frontier:
        frontier(rounds=rounds, verbose=verbose)
    from benchmarks.gen_report import write_bench_comm

    path = write_bench_comm()  # no-op if the frontier artifact is absent
    if verbose and path:
        print("wrote", path)
    return rows


def main():
    run()


if __name__ == "__main__":
    enable_compile_cache()
    main()
