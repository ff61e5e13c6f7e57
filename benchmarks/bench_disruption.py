"""Paper Fig. 1: the round-1 synchronization disruption.

100-node (reduced: 24) Barabási-Albert graph, IID data, heterogeneous init:
DecHetero's accuracy collapses right after the first aggregation while
FedAvg (common init) and DecDiff+VT do not."""
from __future__ import annotations

import argparse

import numpy as np

from benchmarks.common import save_results
from repro.data import make_dataset
from repro.data.allocation import split_by_allocation
from repro.engine import Experiment, Schedule, World
from repro.graphs import make_topology
from repro.models.mlp_cnn import model_for_dataset
from repro.utils.compile_cache import enable_compile_cache


def run(num_nodes=24, rounds=8, data_scale=0.06, verbose=True):
    ds = make_dataset("synth-mnist", seed=0, scale=data_scale)
    topo = make_topology("barabasi_albert", n=num_nodes, m=2, seed=0)
    # IID allocation (the paper's Fig. 1 uses IID to isolate the init effect)
    rng = np.random.default_rng(0)
    order = rng.permutation(len(ds.y_train))
    alloc = np.array_split(order, num_nodes)
    xs, ys = split_by_allocation(ds.x_train, ds.y_train, [np.sort(a) for a in alloc])
    model = model_for_dataset("synth-mnist", ds.num_classes)

    out = {}
    for method in ("dechetero", "fedavg", "decdiff+vt"):
        sim = Experiment(
            World(model=model, topo=topo, xs=xs, ys=ys,
                  x_test=ds.x_test, y_test=ds.y_test),
            method, schedule=Schedule(rounds=rounds, eval_every=1),
            steps_per_round=8, batch_size=32, lr=0.1, momentum=0.9)
        hist = sim.run()
        out[method] = [{"round": m.round, "acc": m.acc_mean} for m in hist]
        if verbose:
            accs = ", ".join(f"{h['acc']:.3f}" for h in out[method])
            print(f"[disruption] {method:12s} acc/round: {accs}")
    # headline numbers: drop between round 0 and round 1
    summary = {m: out[m][0]["acc"] - out[m][1]["acc"] for m in out}
    save_results("disruption", {"curves": out, "round0_to_1_drop": summary})
    return out, summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=8)
    args = ap.parse_args()
    _, summary = run(rounds=args.rounds)
    print("round-0 -> round-1 accuracy drop (positive = disruption):")
    for m, d in summary.items():
        print(f"  {m:12s} {d:+.4f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
