"""Run the DFL engine's main path on a TPU and check what comes out.

    python chip_smoke.py [--seed 0]           # one chip: kernel checks + 3 phases
    python chip_smoke.py --chips 4 [--seed 0]  # four chips: shard_map vs vmap

One process holds the chip(s) throughout and starts no children.  Every
phase goes through the engine's front door, `Experiment(...).compile()` and
`.run()` with `Schedule(mode="fused")`, on the paper's world: synthetic
MNIST at full size (60k/10k samples), 50 nodes on an Erdős–Rényi p=0.2
graph, the paper's MLP 784-512-256-128-10 (567,434 params), DecDiff+VT,
3 fused rounds x 4 local steps, an eval at the last round.

  kernels              segment_neighbor_avg at the phase's widths against
                       plain jnp
  paper-mlp            per-node fp32 transport, vmap, dense layout
  paper-mlp-edge-int8  per-edge int8 adaptive transport (per-link state and
                       the reverse-slot row gather)
  paper-mlp-sparse     sparse layout, per-edge int8 adaptive, EdgeDropout(0.2)
                       (the degree-bucketed reduce)

With `--chips 4` only the pod comparison runs: the paper-mlp world cut to
48 nodes so that it tiles 4 pods, `backend="shard_map"` on an explicit
4-device pod mesh against `backend="vmap"` on the same world, and the
largest absolute difference between their params.

Each phase prints one JSON line (compile s, wall s, node-steps/s, the
process's peak device bytes so far).  A phase that fails raises; the last
line, printed only when all passed, is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
Without a TPU the script exits non-zero before it runs anything.  The
compile cache is `JAX_COMPILATION_CACHE_DIR` when set, else
`<checkout>/.jax_cache`.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import re
import sys
import time

import numpy as np

ROUNDS = 3
LOCAL_STEPS = 4
CHANCE = 0.1  # ten classes
# segment_neighbor_avg vs jnp einsum, both fp32 at HIGHEST precision: the
# two accumulate K <= 16 products in different orders
REDUCE_RTOL = 1e-5
REDUCE_ATOL = 1e-5


def emit(record):
    print(json.dumps(record), flush=True)


def peak_bytes(device):
    return int(device.memory_stats()["peak_bytes_in_use"])


def kernel_names(hlo: str):
    """Names of the Pallas kernels compiled into an optimized HLO module."""
    return sorted({m.group(1) for m in re.finditer(
        r"%([A-Za-z_]+)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        hlo)})


def check_kernels(key, n, k, d):
    """The main-path kernel on the chip at the paper's widths: the reduce
    agrees with the batched einsum within fp32 tolerance."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import segment_neighbor_avg

    kv, kw, km = jax.random.split(key, 3)
    vals = jax.random.normal(kv, (n, k, d), jnp.float32)
    w = (jax.random.uniform(kw, (n, k), jnp.float32)
         * jax.random.bernoulli(km, 0.7, (n, k)))
    t0 = time.perf_counter()
    sums, tot = jax.block_until_ready(segment_neighbor_avg(vals, w))
    reduce_s = time.perf_counter() - t0
    ref = jnp.einsum("bk,bkd->bd", w, vals,
                     precision=jax.lax.Precision.HIGHEST)
    reduce_err = float(jnp.max(jnp.abs(sums - ref)))
    reduce_ok = bool(jnp.all(jnp.abs(sums - ref)
                             <= REDUCE_ATOL + REDUCE_RTOL * jnp.abs(ref)))
    tot_ok = bool(jnp.allclose(tot, jnp.sum(w, axis=1), rtol=REDUCE_RTOL,
                               atol=REDUCE_ATOL))
    del vals, sums, ref
    emit({"phase": "kernels", "reduce_shape": [n, k, d],
          "reduce_max_abs_err": reduce_err, "reduce_rtol": REDUCE_RTOL,
          "reduce_atol": REDUCE_ATOL, "reduce_ok": reduce_ok,
          "totals_ok": tot_ok, "first_call_s": reduce_s})
    assert reduce_ok and tot_ok, "segment_neighbor_avg disagrees with jnp"


def run_phase(name, world, device, expect_kernels, **exp_kwargs):
    """One fused schedule through the front door; returns the Experiment."""
    import jax

    from repro.engine import Experiment, Schedule

    exp = Experiment(world, "decdiff+vt",
                     schedule=Schedule(rounds=ROUNDS, eval_every=ROUNDS,
                                       mode="fused"),
                     steps_per_round=LOCAL_STEPS, batch_size=32, lr=0.1,
                     momentum=0.9, beta=0.95, **exp_kwargs)
    t0 = time.perf_counter()
    compiled = exp.compile()
    compile_s = time.perf_counter() - t0
    kernels = kernel_names(compiled.as_text())
    t0 = time.perf_counter()
    hist = exp.run()
    wall_s = time.perf_counter() - t0

    last = hist[-1]
    losses = np.stack([m.loss_per_node for m in hist])
    emit({"phase": name, "backend": exp.backend, "layout": exp.layout,
          "transport": type(exp.transport).__name__, "nodes": exp.n,
          "params_per_node": sum(int(leaf.size) for leaf in
                                 jax.tree.leaves(exp.params)) // exp.n,
          "rounds": ROUNDS, "local_steps": LOCAL_STEPS,
          "compile_s": compile_s, "wall_s": wall_s,
          "node_steps_per_s": ROUNDS * exp.n * LOCAL_STEPS / wall_s,
          "peak_bytes_in_use": peak_bytes(device), "kernels": kernels,
          "acc_mean": last.acc_mean, "eval_loss_mean": float(losses[-1].mean())})
    missing = [k for k in expect_kernels if not any(k in n for n in kernels)]
    assert not missing, f"{name}: kernels {missing} not compiled in {kernels}"
    assert np.all(np.isfinite(losses)), f"{name}: non-finite eval loss"
    assert last.round == ROUNDS - 1, f"{name}: no eval at the last round"
    assert last.acc_mean > CHANCE, \
        f"{name}: node-average accuracy {last.acc_mean} not above chance"
    return exp


def one_chip(args, device):
    import jax

    from repro.comm import CommConfig
    from repro.dynamics import EdgeDropout
    from repro.engine import World

    world = World.synthetic("synth-mnist", nodes=50, topology="erdos_renyi",
                            p=0.2, scale=1.0, seed=args.seed)
    k = int(world.topo.neighbor_idx.shape[1])  # the dense slot width
    d = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(
        jax.eval_shape(world.model.init, jax.random.PRNGKey(0))))
    check_kernels(jax.random.PRNGKey(args.seed), world.topo.num_nodes, k, d)

    phases = [
        ("paper-mlp", world, ["segment_avg"],
         dict(comm=CommConfig(codec="fp32"))),
        ("paper-mlp-edge-int8", world, ["segment_avg"],
         dict(comm=CommConfig(codec="int8", policy="adaptive"))),
        ("paper-mlp-sparse",
         dataclasses.replace(world, dynamics=EdgeDropout(0.2)),
         ["segment_avg"],
         dict(comm=CommConfig(codec="int8", policy="adaptive"),
              layout="sparse")),
    ]
    for name, w, expect, kw in phases:
        exp = run_phase(name, w, device, expect, **kw)
        del exp
        gc.collect()


def four_chips(args, device):
    import jax

    from repro.comm import CommConfig
    from repro.dist.sharding import make_mesh
    from repro.engine import World

    world = World.synthetic("synth-mnist", nodes=48, topology="erdos_renyi",
                            p=0.2, scale=1.0, seed=args.seed)
    mesh = make_mesh((4,), ("pod",), devices=jax.devices()[:4])
    mesh_devices = {dv.id for dv in mesh.devices.flat}
    assert len(mesh_devices) == 4, f"pod mesh spans {mesh_devices}"
    comm = CommConfig(codec="fp32")
    smap = run_phase("pods-shard_map", world, device, ["segment_avg"],
                     comm=comm, backend="shard_map", mesh=mesh)
    param_devices = set().union(*(leaf.sharding.device_set
                                  for leaf in jax.tree.leaves(smap.params)))
    assert len(param_devices) == 4, f"params span {param_devices}"
    got = jax.tree.map(np.asarray, smap.params)
    del smap
    gc.collect()
    vmap = run_phase("pods-vmap", world, device, ["segment_avg"], comm=comm)
    ref = jax.tree.map(np.asarray, vmap.params)
    diffs = [float(np.max(np.abs(a - b)))
             for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref))]
    emit({"phase": "pods-compare", "mesh_devices": sorted(mesh_devices),
          "param_devices": sorted(dv.id for dv in param_devices),
          "max_abs_diff": max(diffs),
          "bit_equal": all(np.array_equal(a, b) for a, b in
                           zip(jax.tree.leaves(got), jax.tree.leaves(ref)))})


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU; JAX found {devices[0].platform!r}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devices)} device(s)")

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.utils.compile_cache import enable_compile_cache

    emit({"compile_cache": enable_compile_cache(),
          "device_kind": devices[0].device_kind, "devices": len(devices)})
    (one_chip if args.chips == 1 else four_chips)(args, devices[0])
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}})


if __name__ == "__main__":
    main()
