"""Beyond-paper: topology sensitivity of DecDiff+VT.

The paper fixes ER(50, 0.2) and defers topology effects to future work
([29],[30]).  This bench runs DecDiff+VT (and DecHetero as contrast) over
four network families at matched node count and reports final accuracy and
a mixing proxy (spectral gap of the normalized adjacency) — quantifying how
knowledge spread depends on the communication graph.
"""
from __future__ import annotations

import argparse

import numpy as np

from benchmarks.common import save_results
from repro.data import make_dataset, zipf_allocation
from repro.data.allocation import split_by_allocation
from repro.engine import Experiment, Schedule, World
from repro.graphs import make_topology
from repro.models.mlp_cnn import model_for_dataset
from repro.utils.compile_cache import enable_compile_cache

TOPOLOGIES = [
    ("erdos_renyi", dict(p=0.25)),
    ("barabasi_albert", dict(m=2)),
    ("watts_strogatz", dict(k=4, p=0.2)),
    ("ring", dict()),
]


def spectral_gap(topo) -> float:
    a = topo.adjacency.astype(np.float64)
    d = np.maximum(a.sum(1), 1)
    p = a / d[:, None]
    ev = np.sort(np.abs(np.linalg.eigvals(p)))[::-1]
    return float(1.0 - ev[1])


def run(num_nodes=16, rounds=40, data_scale=0.04, methods=("decdiff+vt", "dechetero"),
        verbose=True):
    ds = make_dataset("synth-mnist", seed=0, scale=data_scale)
    model = model_for_dataset("synth-mnist", ds.num_classes)
    rows = []
    for name, kw in TOPOLOGIES:
        topo = make_topology(name, n=num_nodes, seed=0, **kw)
        alloc = zipf_allocation(ds.y_train, num_nodes, seed=0, min_per_class=1)
        xs, ys = split_by_allocation(ds.x_train, ds.y_train, alloc)
        gap = spectral_gap(topo)
        for method in methods:
            sim = Experiment(
                World(model=model, topo=topo, xs=xs, ys=ys,
                      x_test=ds.x_test, y_test=ds.y_test),
                method, schedule=Schedule(rounds=rounds, eval_every=rounds),
                steps_per_round=4, batch_size=32, lr=0.1, momentum=0.9)
            hist = sim.run()
            rows.append({"topology": topo.name, "spectral_gap": gap,
                         "method": method, "acc": hist[-1].acc_mean,
                         "acc_std": hist[-1].acc_std,
                         "max_degree": topo.max_degree})
            if verbose:
                print(f"[topo] {topo.name:28s} gap={gap:.3f} {method:12s} "
                      f"acc={hist[-1].acc_mean:.4f}")
    save_results("topology_table", rows)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=40)
    args = ap.parse_args()
    run(rounds=args.rounds)


if __name__ == "__main__":
    enable_compile_cache()
    main()
