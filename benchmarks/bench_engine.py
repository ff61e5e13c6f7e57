"""Engine runner throughput: the scan-fused schedule vs the per-round loop.

The first perf artifact of this repo's runner layer.  One seeded 16-node
Barabási–Albert smoke world (the comm smoke config scaled to 16 nodes),
DecDiff+VT, identical math on every row — `Schedule(mode=...)` is pinned
bit-identical across modes by tests/test_engine.py — so the only thing
measured is the execution strategy:

  * ``loop``  — one jitted XLA dispatch per round + a jitted eval call at
    the eval cadence;
  * ``fused`` — the whole schedule (K rounds + flag-gated evals) compiled
    into ONE `lax.scan` program and dispatched once.

Reported per mode: rounds/sec (after a full warmup run that absorbs
compilation) and the compile+first-run wall time, on both backends where
the host allows.  When a pod axis exists, the shard_map exchange is also
timed on BOTH wires — ``encoded`` (the default: codec payload crosses the
pod axis, every pod decodes the gathered bytes) vs ``decoded`` (the
oracle: fp32 rows cross) — with an int8 event-triggered transport, so the
artifact records that the fused encoded default is no slower.
`gen_report.write_bench_engine()` folds the sweep into BENCH_engine.json
with the acceptance gates: fused >= 2x loop rounds/sec on the vmap
backend, and encoded >= 0.9x decoded rounds/sec on shard_map.

    PYTHONPATH=src python -m benchmarks.bench_engine [--rounds 60] [--smoke]

``--smoke`` shrinks the sweep (8 rounds, 1 timed repeat) and writes the
``engine_smoke`` artifact instead of the committed one — the CI multihost
lane uses it to exercise the shard_map encoded-payload path end to end.
"""
from __future__ import annotations

import argparse
import time

from benchmarks.common import load_results, save_results
from repro.comm import CommConfig
from repro.engine import Experiment, Schedule, World
from repro.utils.compile_cache import enable_compile_cache

ROUNDS = 60
EVAL_EVERY = 10
TIMED_REPEATS = 3  # best-of: the 2-core CPU container is a noisy neighbour


def smoke_world16(seed=0):
    """16-node BA scale-free world over reduced synth-mnist, the paper's
    Table-I MLP — the ISSUE-4 acceptance world."""
    from repro.models.mlp_cnn import make_mlp

    return World.synthetic(dataset="synth-mnist", nodes=16,
                           topology="barabasi_albert", m=2, seed=seed,
                           scale=0.03,
                           model=make_mlp(num_classes=10, hidden=(64, 32)))


def _time_mode(world, mode, backend, rounds, eval_every, seed=0,
               comm=None, wire="encoded", repeats=TIMED_REPEATS):
    exp = Experiment(world, "decdiff+vt", backend=backend, comm=comm,
                     wire=wire,
                     schedule=Schedule(rounds=rounds, eval_every=eval_every,
                                       mode=mode),
                     steps_per_round=4, batch_size=32, lr=0.1, momentum=0.9,
                     seed=seed)
    t0 = time.perf_counter()
    exp.run()  # compile + warmup (state evolves; the timed runs continue)
    compile_s = time.perf_counter() - t0
    wall = float("inf")
    for _ in range(repeats):  # best-of: de-noise the shared CPU
        t0 = time.perf_counter()
        hist = exp.run()
        wall = min(wall, time.perf_counter() - t0)
    return {
        "mode": mode, "backend": backend, "rounds": rounds,
        "eval_every": eval_every,
        "wire": wire,
        "comm": None if comm is None else "int8+trigger",
        "rounds_per_sec": rounds / wall,
        "wall_s": wall,
        "timed_repeats": repeats,
        "compile_and_first_run_s": compile_s,
        "final_acc": hist[-1].acc_mean,
    }


def run(rounds=ROUNDS, eval_every=EVAL_EVERY, seed=0, verbose=True,
        smoke=False):
    import jax

    repeats = 1 if smoke else TIMED_REPEATS
    world = smoke_world16(seed)
    rows = []
    backends = ["vmap"]
    # the shard_map lowering is also timed when a pod axis exists (on the
    # forced 4-device CPU mesh this measures lowering overhead, not a real
    # interconnect — recorded for trend, excluded from the acceptance gate)
    if len(jax.devices()) > 1 and 16 % len(jax.devices()) == 0:
        backends.append("shard_map")
    for backend in backends:
        for mode in ("loop", "fused"):
            row = _time_mode(world, mode, backend, rounds, eval_every,
                             seed=seed, repeats=repeats)
            rows.append(row)
            if verbose:
                print(f"[{backend:>9}/{mode:5}] {row['rounds_per_sec']:8.1f} "
                      f"rounds/s  (timed {row['wall_s']:.2f}s over {rounds} "
                      f"rounds, compile+warmup {row['compile_and_first_run_s']:.2f}s, "
                      f"final acc {row['final_acc']:.4f})", flush=True)
    by = {(r["backend"], r["mode"]): r for r in rows}
    speedup = (by[("vmap", "fused")]["rounds_per_sec"]
               / by[("vmap", "loop")]["rounds_per_sec"])
    if verbose:
        print(f"scan-fused speedup (vmap): {speedup:.2f}x")

    # wire comparison: the fused encoded-payload shard_map exchange (the
    # default) vs the decoded-rows oracle, int8 event-triggered transport.
    wire_rows, wire_ratio = [], None
    if "shard_map" in backends:
        comm = CommConfig(codec="int8", trigger_threshold=1.0)
        for wire in ("encoded", "decoded"):
            row = _time_mode(world, "fused", "shard_map", rounds, eval_every,
                             seed=seed, comm=comm, wire=wire,
                             repeats=repeats if smoke else 2 * TIMED_REPEATS)
            wire_rows.append(row)
            if verbose:
                print(f"[shard_map/fused/int8 wire={wire:7}] "
                      f"{row['rounds_per_sec']:8.1f} rounds/s", flush=True)
        wire_ratio = (wire_rows[0]["rounds_per_sec"]
                      / wire_rows[1]["rounds_per_sec"])
        if verbose:
            print(f"encoded/decoded rounds-per-sec ratio (shard_map): "
                  f"{wire_ratio:.2f}x")

    payload = {
        "world": {"graph": "barabasi_albert(n=16, m=2, seed=%d)" % seed,
                  "dataset": "synth-mnist(scale=0.03)",
                  "model": "mlp(64, 32)", "method": "decdiff+vt",
                  "steps_per_round": 4, "batch_size": 32},
        "rows": rows,
        "wire_rows": wire_rows,
        "fused_speedup_vmap": speedup,
        "encoded_over_decoded_shardmap": wire_ratio,
    }
    if smoke:
        # CI artifact only — the committed BENCH_engine.json is refreshed
        # by the full bench, never by the smoke lane.
        save_results("engine_smoke", payload)
        return payload
    save_results("engine_runner", payload)
    from benchmarks.gen_report import write_bench_engine

    path = write_bench_engine()
    if verbose and path:
        print("wrote", path)
    return payload


def run_wire_only(rounds=ROUNDS, eval_every=EVAL_EVERY, seed=0,
                 verbose=True):
    """Refresh ONLY the wire-comparison rows of the engine_runner artifact.

    The main backend/mode sweep is timed on the natural host (no forced
    device count — that splits the CPU threadpool and distorts the vmap
    numbers the 2x schedule gate is defined over), while the wire rows
    need a pod axis.  So the committed artifact is produced in two runs:
    the full bench on the natural host, then this under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
    """
    import jax

    if len(jax.devices()) < 2 or 16 % len(jax.devices()) != 0:
        raise SystemExit("--wire-only needs a pod axis (run under "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    res = load_results("engine_runner")
    if not res:
        raise SystemExit("engine_runner artifact missing; run the full "
                         "bench first")
    world = smoke_world16(seed)
    comm = CommConfig(codec="int8", trigger_threshold=1.0)
    wire_rows = []
    for wire in ("encoded", "decoded"):
        row = _time_mode(world, "fused", "shard_map", rounds, eval_every,
                         seed=seed, comm=comm, wire=wire,
                         repeats=2 * TIMED_REPEATS)
        wire_rows.append(row)
        if verbose:
            print(f"[shard_map/fused/int8 wire={wire:7}] "
                  f"{row['rounds_per_sec']:8.1f} rounds/s", flush=True)
    ratio = wire_rows[0]["rounds_per_sec"] / wire_rows[1]["rounds_per_sec"]
    if verbose:
        print(f"encoded/decoded rounds-per-sec ratio (shard_map): "
              f"{ratio:.2f}x")
    res["wire_rows"] = wire_rows
    res["encoded_over_decoded_shardmap"] = ratio
    save_results("engine_runner", res)
    from benchmarks.gen_report import write_bench_engine

    path = write_bench_engine()
    if verbose and path:
        print("wrote", path)
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=ROUNDS)
    ap.add_argument("--eval-every", type=int, default=EVAL_EVERY)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep (8 rounds, 1 repeat); writes the "
                         "engine_smoke artifact only")
    ap.add_argument("--wire-only", action="store_true",
                    help="refresh only the encoded-vs-decoded wire rows of "
                         "the engine_runner artifact (needs a pod axis)")
    args = ap.parse_args()
    if args.smoke:
        run(rounds=8, eval_every=8, seed=args.seed, smoke=True)
    elif args.wire_only:
        run_wire_only(rounds=args.rounds, eval_every=args.eval_every,
                      seed=args.seed)
    else:
        run(rounds=args.rounds, eval_every=args.eval_every, seed=args.seed)


if __name__ == "__main__":
    enable_compile_cache()
    main()
