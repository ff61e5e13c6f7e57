"""Paper Table III (ablation): DecHetero -> DecDiff -> DecDiff+VT, isolating
the aggregation-function contribution from the virtual-teacher contribution.
Beyond-paper rows: VT grafted onto the baselines (dechetero+vt, cfa+vt)."""
from __future__ import annotations

import argparse

from benchmarks.common import WorldConfig, build_world, run_method, save_results
from repro.utils.compile_cache import enable_compile_cache

ROWS = ["dechetero", "decdiff", "decdiff+vt", "dechetero+vt", "cfa", "cfa+vt"]


def run(dataset="synth-mnist", rounds=60, num_nodes=30, data_scale=0.08,
        verbose=True):
    wc = WorldConfig(dataset=dataset, rounds=rounds, num_nodes=num_nodes,
                     data_scale=data_scale)
    world = build_world(wc)
    results = {"_world": {"gini": world[5], "dataset": dataset, "rounds": rounds}}
    for method in ROWS:
        results[method] = run_method(wc, method, world=world)
        if verbose:
            print(f"[ablation] {method:14s} acc={results[method]['acc_mean']:.4f}")
    save_results("ablation_table", results)
    return results


def format_table(results) -> str:
    base = results["dechetero"]["acc_mean"]
    lines = ["| method | loss | aggregation | avg acc | gain vs DecHetero [%pt] |",
             "|---|---|---|---|---|"]
    meta = {
        "dechetero": ("CE", "DecAvg"), "decdiff": ("CE", "DecDiff"),
        "decdiff+vt": ("VT", "DecDiff"), "dechetero+vt": ("VT", "DecAvg"),
        "cfa": ("CE", "CFA"), "cfa+vt": ("VT", "CFA"),
    }
    for m in ROWS:
        if m not in results:
            continue
        acc = results[m]["acc_mean"]
        loss, agg = meta[m]
        lines.append(f"| {m} | {loss} | {agg} | {acc:.4f} | "
                     f"{100 * (acc - base):+.2f} |")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=60)
    args = ap.parse_args()
    print(format_table(run(rounds=args.rounds)))


if __name__ == "__main__":
    enable_compile_cache()
    main()
