"""Paper Table II (+ Fig. 5/6 statistics): final average accuracy per method.

Runs the full method roster on one world instance per dataset and reports the
Table II layout (standalone baselines / partially-decentralized / SOTA DFL /
proposal).  Characteristic times (Table IV) are derived from the same
histories by bench_char_time.py — run this first.
"""
from __future__ import annotations

import argparse

import numpy as np

from benchmarks.common import (
    WorldConfig,
    build_world,
    run_centralized,
    run_method,
    save_results,
)
from repro.utils.compile_cache import enable_compile_cache

METHODS = ["isol", "fedavg", "dechetero", "cfa", "cfa-ge", "decdiff", "decdiff+vt"]


def run(datasets=("synth-mnist",), rounds=60, num_nodes=30, data_scale=0.08,
        verbose=True):
    all_results = {}
    for dataset in datasets:
        wc = WorldConfig(dataset=dataset, rounds=rounds, num_nodes=num_nodes,
                         data_scale=data_scale)
        world = build_world(wc)
        results = {"_world": {"gini": world[5], "nodes": num_nodes,
                              "rounds": rounds, "dataset": dataset,
                              "data_scale": data_scale}}
        results["centralized"] = run_centralized(wc, world=world)
        if verbose:
            print(f"[{dataset}] centralized acc={results['centralized']['acc_mean']:.4f}")
        for method in METHODS:
            results[method] = run_method(wc, method, world=world)
            if verbose:
                r = results[method]
                print(f"[{dataset}] {method:12s} acc={r['acc_mean']:.4f} "
                      f"±{r['acc_std']:.4f}  ({r['wall_s']:.0f}s)")
        all_results[dataset] = results
    save_results("accuracy_table", all_results)
    return all_results


def format_table(all_results) -> str:
    lines = ["| dataset | method | avg acc | ±std | node-wise IQR |",
             "|---|---|---|---|---|"]
    for dataset, results in all_results.items():
        for method, r in results.items():
            if method.startswith("_"):
                continue
            iqr = ""
            if "acc_per_node" in r:
                q = np.percentile(r["acc_per_node"], [25, 75])
                iqr = f"{q[1] - q[0]:.3f}"
            lines.append(f"| {dataset} | {method} | {r['acc_mean']:.4f} | "
                         f"{r.get('acc_std', 0):.4f} | {iqr} |")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--datasets", nargs="+", default=["synth-mnist"])
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--nodes", type=int, default=30)
    ap.add_argument("--scale", type=float, default=0.08)
    args = ap.parse_args()
    res = run(args.datasets, args.rounds, args.nodes, args.scale)
    print(format_table(res))


if __name__ == "__main__":
    enable_compile_cache()
    main()
