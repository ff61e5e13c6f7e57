"""Node-axis scaling: the dense padded engine vs the sparse edge-list one.

The dense layout carries O(N·max_deg) neighbour state and gathers an
[N, max_deg, D] value block every round — on a scale-free graph max_deg
grows with N, so the block is effectively O(N^2·D) and the engine hits a
memory wall around a few thousand nodes.  The sparse layout
(`Experiment(layout="sparse")` over a `repro.graphs.SparseTopology`) keeps
O(N + E) edge state and reduces degree-bucketed ragged blocks, so the node
axis extends to 10^4 engine nodes (and 10^5-10^6 for the graph builders
and the reduce kernel alone) on this 2-core CPU container.

Three tiers, recorded in one artifact (four with ``--dynamics``):

  * engine rounds/sec: a tiny-MLP gossip world (DecDiff), swept over N for
    BOTH layouts; dense stops where its padded block would not fit (the
    row records the projected bytes instead of crashing the host);
  * kernel reduce: `segment_neighbor_avg` walltime at 10^5 receivers;
  * graph build: `sparse_barabasi_albert` walltime at 10^6 nodes;
  * ``--dynamics``: the lifted sparse scenario cube at scale — DecDiff
    through the int8+adaptive PER-EDGE transport under 20% i.i.d. edge
    dropout at 10^4 nodes, sparse layout (the dense engine is
    memory-walled there; at oracle sizes the two are bit-identical, see
    tests/test_sparse_parity.py).

    PYTHONPATH=src python -m benchmarks.bench_scale [--smoke] [--dynamics]

``--smoke`` runs [64, 256] nodes x both layouts (plus downscaled kernel/
builder/dynamics tiers) and writes the ``scale_smoke`` artifact only — the
committed BENCH_scale.json is refreshed by the full bench via
`gen_report.write_bench_scale()`.
"""
from __future__ import annotations

import argparse
import dataclasses
import resource
import time

import numpy as np

from benchmarks.common import save_results
from repro.utils.compile_cache import enable_compile_cache

# dense is swept while its padded neighbour block stays under this budget;
# past it the row records the projection, not an OOM.
DENSE_BYTES_BUDGET = int(1.5e9)
ENGINE_NODES = (64, 256, 1024, 4096, 10000)
SMOKE_NODES = (64, 256)
ROUNDS = 3


def _maxrss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def tiny_world(n: int, seed: int = 0):
    """A minimal gossip world sized for the node axis, not the model axis:
    16-dim inputs, 4 samples per node, one-hidden-layer MLP (~850 params) —
    per-round cost is then dominated by the neighbour exchange, which is
    the thing this bench scales."""
    from repro.engine import World
    from repro.graphs.sparse import sparse_barabasi_albert
    from repro.models.mlp_cnn import make_mlp

    rng = np.random.default_rng(seed)
    dim, per_node, classes = 16, 4, 10
    xs = [rng.normal(size=(per_node, dim)).astype(np.float32)
          for _ in range(n)]
    ys = [rng.integers(0, classes, size=per_node).astype(np.int32)
          for _ in range(n)]
    x_test = rng.normal(size=(64, dim)).astype(np.float32)
    y_test = rng.integers(0, classes, size=64).astype(np.int32)
    st = sparse_barabasi_albert(n=n, m=2, seed=seed)
    model = make_mlp(num_classes=classes, input_dim=dim, hidden=(32,))
    return World(model=model, topo=st, xs=xs, ys=ys,
                 x_test=x_test, y_test=y_test), st


def dense_block_bytes(st, d_model: int) -> float:
    """The dense exchange's dominant allocation: the [N, max_deg, D] f32
    gathered-neighbour block (the padded index/weight panels are the same
    shape sans D)."""
    return 4.0 * st.num_nodes * st.max_degree * d_model


def _time_engine(world, layout: str, rounds: int, seed: int = 0):
    from repro.engine import Experiment, Schedule

    exp = Experiment(world, "decdiff", layout=layout,
                     schedule=Schedule(rounds=rounds, eval_every=rounds,
                                       mode="loop"),
                     steps_per_round=1, batch_size=4, eval_batch=64,
                     lr=0.1, seed=seed)
    exp.run()  # compile + warmup
    t0 = time.perf_counter()
    exp.run()
    wall = time.perf_counter() - t0
    return rounds / wall, wall


def engine_sweep(nodes, rounds: int, seed: int = 0, verbose: bool = True):
    import jax

    d_model = None
    rows = []
    for n in nodes:
        world, st = tiny_world(n, seed)
        if d_model is None:
            p = world.model.init(jax.random.PRNGKey(0))
            d_model = int(sum(np.prod(l.shape, dtype=int)
                              for l in jax.tree.leaves(p)))
        for layout in ("dense", "sparse"):
            row = {"nodes": n, "layout": layout, "d_model": d_model,
                   "edges_directed": st.num_directed,
                   "max_degree": st.max_degree,
                   "dense_block_bytes": dense_block_bytes(st, d_model),
                   "rounds": rounds}
            if (layout == "dense"
                    and row["dense_block_bytes"] > DENSE_BYTES_BUDGET):
                row["skipped"] = (
                    f"projected dense neighbour block "
                    f"{row['dense_block_bytes'] / 1e9:.1f} GB exceeds the "
                    f"{DENSE_BYTES_BUDGET / 1e9:.1f} GB budget")
                if verbose:
                    print(f"[n={n:6d} {layout:6}] SKIP ({row['skipped']})",
                          flush=True)
            else:
                rps, wall = _time_engine(world, layout, rounds, seed)
                row.update(rounds_per_sec=rps, wall_s=wall,
                           maxrss_mb=_maxrss_mb())
                if verbose:
                    print(f"[n={n:6d} {layout:6}] {rps:7.2f} rounds/s  "
                          f"(maxrss {row['maxrss_mb']:.0f} MB)", flush=True)
            rows.append(row)
    return rows


def kernel_tier(receivers: int = 100_000, width: int = 8, d: int = 256,
                verbose: bool = True):
    """The ragged reduce alone at 10^5 receivers (no training loop)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import segment_neighbor_avg

    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.normal(size=(receivers, width, d))
                       .astype(np.float32))
    w = jnp.asarray(rng.random((receivers, width)).astype(np.float32))
    sums, tot = segment_neighbor_avg(vals, w)  # compile + warmup
    jax.block_until_ready((sums, tot))
    t0 = time.perf_counter()
    sums, tot = segment_neighbor_avg(vals, w)
    jax.block_until_ready((sums, tot))
    wall = time.perf_counter() - t0
    row = {"receivers": receivers, "width": width, "d": d, "wall_s": wall,
           "edges_per_sec": receivers * width / wall}
    if verbose:
        print(f"[kernel n={receivers} k={width} d={d}] {wall:.2f}s "
              f"({row['edges_per_sec'] / 1e6:.2f}M edge-slots/s)", flush=True)
    return row


def builder_tier(n: int = 1_000_000, verbose: bool = True):
    """Vectorized sparse BA builder at the 10^6-node tier."""
    from repro.graphs.sparse import sparse_barabasi_albert

    t0 = time.perf_counter()
    st = sparse_barabasi_albert(n=n, m=2, seed=0, ensure_connected=False)
    wall = time.perf_counter() - t0
    row = {"nodes": n, "edges_directed": st.num_directed,
           "max_degree": st.max_degree, "wall_s": wall,
           "nodes_per_sec": n / wall}
    if verbose:
        print(f"[builder ba n={n}] {wall:.2f}s "
              f"(max_degree {st.max_degree})", flush=True)
    return row


def dynamics_tier(n: int = 10_000, rounds: int = ROUNDS, seed: int = 0,
                  verbose: bool = True):
    """The whole lifted scenario cube in one record: `layout="sparse"` x
    per-edge adaptive int8 transport x `EdgeDropout(p=0.2)` — the three
    combinations the sparse engine used to reject at construction, composed,
    at a node count the dense engine cannot reach."""
    from repro.comm import CommConfig
    from repro.dynamics import EdgeDropout
    from repro.engine import Experiment, Schedule

    world, st = tiny_world(n, seed)
    world = dataclasses.replace(world, dynamics=EdgeDropout(p=0.2))
    comm = CommConfig(codec="int8", policy="adaptive", target_trigger=0.6,
                      per_edge=True)
    exp = Experiment(world, "decdiff", layout="sparse", comm=comm,
                     schedule=Schedule(rounds=rounds, eval_every=rounds,
                                       mode="loop"),
                     steps_per_round=1, batch_size=4, eval_batch=64,
                     lr=0.1, seed=seed)
    exp.run()  # compile + warmup
    t0 = time.perf_counter()
    exp.run()
    wall = time.perf_counter() - t0
    row = {"nodes": n, "edges_directed": st.num_directed,
           "layout": "sparse", "method": "decdiff",
           "transport": "per-edge int8 adaptive (target_trigger=0.6)",
           "dropout_p": 0.2, "rounds": rounds,
           "rounds_per_sec": rounds / wall, "wall_s": wall,
           "maxrss_mb": _maxrss_mb(),
           "live_frac_mean": float(np.mean(exp.live_history[-rounds:])),
           "trig_frac_mean": float(np.mean(exp.trig_history[-rounds:])),
           "comm_bytes_total": int(exp.comm_bytes_total)}
    if verbose:
        print(f"[dynamics n={n} sparse int8+adaptive drop=0.2] "
              f"{row['rounds_per_sec']:.2f} rounds/s  "
              f"(live {row['live_frac_mean']:.3f}, "
              f"trig {row['trig_frac_mean']:.3f})", flush=True)
    return row


def run(smoke: bool = False, seed: int = 0, verbose: bool = True,
        dynamics: bool = False):
    nodes = SMOKE_NODES if smoke else ENGINE_NODES
    rows = engine_sweep(nodes, ROUNDS, seed=seed, verbose=verbose)
    kernel = kernel_tier(receivers=10_000 if smoke else 100_000,
                         verbose=verbose)
    builder = builder_tier(n=100_000 if smoke else 1_000_000,
                           verbose=verbose)
    dyn_row = (dynamics_tier(n=512 if smoke else 10_000, seed=seed,
                             verbose=verbose) if dynamics else None)
    payload = {
        "world": {"graph": "sparse_barabasi_albert(m=2)",
                  "model": "mlp(16->32->10)", "method": "decdiff",
                  "steps_per_round": 1, "batch_size": 4,
                  "rounds_timed": ROUNDS},
        "dense_bytes_budget": DENSE_BYTES_BUDGET,
        "rows": rows,
        "kernel": kernel,
        "builder": builder,
        "dynamics": dyn_row,
    }
    if smoke:
        # CI artifact only — the committed BENCH_scale.json is refreshed by
        # the full bench, never by the smoke lane.
        save_results("scale_smoke", payload)
        return payload
    save_results("scale_sweep", payload)
    from benchmarks.gen_report import write_bench_scale

    path = write_bench_scale()
    if verbose and path:
        print("wrote", path)
    return payload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="[64, 256] nodes x both layouts + downscaled "
                         "kernel/builder tiers; writes the scale_smoke "
                         "artifact only")
    ap.add_argument("--dynamics", action="store_true",
                    help="add the sparse int8+adaptive-under-dropout tier "
                         "(10^4 nodes; 512 with --smoke)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    run(smoke=args.smoke, seed=args.seed, dynamics=args.dynamics)


if __name__ == "__main__":
    enable_compile_cache()
    main()
