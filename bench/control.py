"""Readings that the correctness limits are set from, at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--program]

For each seed, in one process: the float32 reference over the cell's
set-up calls, then each of these put in the program's place and compared
with it by `bench/correct.py`'s numbers:

  program      the program's own first calls, as a benchmark run takes
               them (with --program)
  bf16         the reference in bfloat16: the lower-precision control
  half_batch   the reference with half of each minibatch left out
  no_exchange  the reference with the gossip step left out

One JSON line per seed and variant.  The lower reading of a number is the
largest that the program gives over a dozen seeds or more; the upper one
the smallest that the control or a fault gives (see PERF.md).  The
benchmark's own runs never run this; `bench/tests/test_control.py` runs it
at a small size.
"""
import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def readings(workload, seeds, program=True,
             variants=("bf16", "half_batch", "no_exchange"), overrides=None,
             emit=print):
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import jax.numpy as jnp

    from bench import correct, drive
    from bench.reference import reference_run
    from bench.run import cache_setup, cell_spec
    from bench.world import build_world
    from repro.utils.compile_cache import enable_compile_cache

    cache_setup(enable_compile_cache)
    spec = cell_spec(workload, overrides)
    cfg, traffic, model = spec["cfg"], spec["traffic"], spec["model"]
    world = build_world(cfg["world"])
    n, rounds = world.num_nodes, traffic["rounds_per_call"]
    calls = traffic["set_up_calls"]
    out = []
    for seed in seeds:
        ref = reference_run(model, cfg, world,
                            drive.make_params(model, cfg, seed, n), rounds,
                            calls)
        found = {}
        if program:
            exp = drive.build_experiment(
                model, cfg, traffic, world,
                drive.make_params(model, cfg, seed, n), seed)
            exp.compile(rounds, rounds)
            prog = drive.set_up_calls(exp, rounds, calls)
            prog.pop("bytes")
            del exp
            gc.collect()
            found["program"] = correct.gaps(prog, ref)
        for v in variants:
            dtype = jnp.bfloat16 if v == "bf16" else jnp.float32
            fault = None if v == "bf16" else v
            got = reference_run(model, cfg, world,
                                drive.make_params(model, cfg, seed, n),
                                rounds, calls, dtype=dtype, fault=fault)
            found[v] = correct.gaps(got, ref)
        for k, g in found.items():
            line = {"workload": workload, "seed": seed, "variant": k,
                    "gaps": g}
            out.append(line)
            emit(json.dumps(line))
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--variants", nargs="*",
                    default=["bf16", "half_batch", "no_exchange"])
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(ROOT, ".bench_work", "tpu_logs"))
    readings(args.workload, args.seeds, args.program, tuple(args.variants),
             emit=lambda s: print(s, flush=True))


if __name__ == "__main__":
    main()
