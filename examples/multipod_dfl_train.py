"""Multi-pod DFL training — the same Experiment spec on the pod mesh.

The `Experiment` that runs vmapped on one host lowers unchanged to the
shard_map backend: each pod (mesh axis "pod") owns a block of nodes' params,
optimizer state and data shards, and the DecDiff gossip exchange is an
all_gather over the pod ring.  The two lowerings are bit-identical
(tests/test_engine.py), so this script is about EXECUTION, not math: run it
under a forced multi-device CPU to watch the same seeded world split over a
real pod axis, scan-fused into one XLA program per schedule.

    PYTHONPATH=src python examples/multipod_dfl_train.py --nodes 8
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/multipod_dfl_train.py --nodes 8

For the LM architectures the same round shape lowers through the legacy
`repro.dist.dfl_step.build_dfl_round_shardmap`, where the all_gather carries
the encoded int8 payload and the dequantize+average is fused into the
`dequant_neighbor_avg_rows` Pallas kernel — that path is pinned by
tests/test_dist.py, not run by this example.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.engine import Experiment, Schedule, World
from repro.utils.pytree import tree_index, tree_l2_dist


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--eval-every", type=int, default=5)
    args = ap.parse_args()

    world = World.synthetic(dataset="synth-mnist", nodes=args.nodes,
                            topology="ring", seed=0, scale=0.03)
    exp = Experiment(world, "decdiff+vt", backend="shard_map",
                     schedule=Schedule(rounds=args.rounds,
                                       eval_every=args.eval_every,
                                       mode="fused"),
                     steps_per_round=4, batch_size=32, lr=0.1, momentum=0.9)
    n_pods = int(exp.mesh.shape["pod"])
    print(f"{len(jax.devices())} device(s) -> {n_pods}-pod mesh, "
          f"{args.nodes // n_pods} nodes per pod (heterogeneous init, "
          f"ring gossip)")

    d0 = float(tree_l2_dist(tree_index(exp.params, 0),
                            tree_index(exp.params, 1)))
    hist = exp.run(verbose=True)
    d1 = float(tree_l2_dist(tree_index(exp.params, 0),
                            tree_index(exp.params, 1)))
    print(f"\nnode0-node1 model distance: init {d0:.2f} -> final {d1:.2f} "
          f"({'converging' if d1 < d0 else 'diverging'}) — DecDiff pulls "
          f"heterogeneously-initialized nodes together without a server, "
          f"final acc {hist[-1].acc_mean:.3f} ± {hist[-1].acc_std:.3f}")


if __name__ == "__main__":
    main()
