"""Run one benchmark cell on the accelerator and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell is one entry of `workloads` in BENCHMARK.json: a configuration
(`bench/configs/<config>.json` with `<config>.py` beside it) under a
traffic mix (`bench/traffic/<traffic>.json`).  What depends on the model
or the data comes from the configuration's module, each part with the
paper's default: its world (`build_world`, else `bench/world.py`'s), its
loss and scoring (`bench/reference.py`).  Per-layer metrics are
readers in `bench/metrics/<metric>.py`, limits of the correctness check
are in `bench/limits/<cell>.json`; the harness finds each by its name.

A run: set-up (world and weights from the seed, `Experiment(...)`, compile
through the persistent cache, the first calls with their readings), then
calls back to back for `--seconds` (traced with `--trace 1`), then the plain
reference over the same first calls and the comparison that decides
`correct`.  With `--trace 0` the metrics are the cell's end-to-end ones,
with `--trace 1` its per-layer ones.  Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(ROOT, ".bench_work")
CACHE_BYTES = 4 << 30


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell needs."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def cell_spec(workload: str, overrides=None, where=None) -> dict:
    """Everything BENCHMARK.json and the named files say about a cell.

    `overrides` ({"world": {...}, "traffic": {...}}) shrinks a cell, and
    `where` names a directory laid out like `bench/` (`cells.json` in
    BENCHMARK.json's shape, with `configs` and `workloads` only, and
    `traffic/`, `limits/`) whose cells run under BENCHMARK.json's
    metrics: both serve the tests under bench/tests; benchmark runs never
    pass them."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells_from = bench if where is None else _json(
        os.path.join(where, "cells.json"))
    where = where or HERE
    cells = {w["name"]: w for w in cells_from["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"cells: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in cells_from["configs"]}[cell["config"]]
    cfg = _json(os.path.join(ROOT, conf["file"]))
    traffic = _json(os.path.join(where, "traffic", f"{cell['traffic']}.json"))
    overrides = overrides or {}
    cfg["world"].update(overrides.get("world", {}))
    traffic.update(overrides.get("traffic", {}))
    model = load_module(os.path.splitext(os.path.join(ROOT, conf["file"]))[0]
                        + ".py", f"bench_config_{cell['config']}")
    return {"bench": bench, "cell": cell, "cfg": cfg, "traffic": traffic,
            "model": model, "where": where}


def make_world(spec):
    """The cell's world: the configuration module's `build_world(world
    block)` where it has one, else the paper's (`bench.world`)."""
    from bench import world

    model = spec["model"]
    build = getattr(model, "build_world", world.build_world)
    return build(spec["cfg"]["world"])


def cache_setup(enable_compile_cache):
    """The program's persistent compile cache, with every program in it:
    the fused program holds the world's arrays as constants (0.6 GB), so
    a size cap below that would leave it out and every run would
    compile."""
    import jax

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cap = jax.config.jax_compilation_cache_max_size
    if 0 < cap < CACHE_BYTES:
        jax.config.update("jax_compilation_cache_max_size", CACHE_BYTES)


def check_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    peaks = _json(os.path.join(HERE, "peaks.json"))["devices"]
    if devs[0].device_kind not in peaks:
        raise NoChip(f"no peaks for device kind {devs[0].device_kind!r} "
                     f"in bench/peaks.json")
    return devs, peaks[devs[0].device_kind]


def _metrics(names, ctx, units, per_layer):
    out = {}
    for name in names:
        if per_layer:
            value = load_module(os.path.join(HERE, "metrics", f"{name}.py"),
                                f"bench_metric_{name}").read(ctx)
        else:
            value = ctx["e2e"][name]
        if value is not None:
            out[name] = {"value": value, "unit": units[name]}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, overrides=None, where=None) -> dict:
    """One run of a cell; returns the result line's object."""
    spec = cell_spec(workload, overrides, where)
    cell, cfg, traffic, model = (spec["cell"], spec["cfg"], spec["traffic"],
                                 spec["model"])
    import jax

    if require_chip:
        devs, peaks = check_devices(cell["chips"])
    else:
        devs, peaks = jax.devices(), None
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.utils.compile_cache import enable_compile_cache

    from bench import correct, drive
    from bench.reference import node_sharding, reference_run

    cache_setup(enable_compile_cache)

    # ---- set-up: world, weights, the one Experiment, compile, first calls
    t0 = time.perf_counter()
    world = make_world(spec)
    n = world.num_nodes
    rounds = traffic["rounds_per_call"]
    place = drive.placement(traffic, cell["chips"])
    params = drive.make_params(model, cfg, seed, n, sharding=place)
    world_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    exp = drive.build_experiment(model, cfg, traffic, world, params, seed,
                                 place)
    jax.block_until_ready(exp.params)
    init_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = exp.compile(rounds, rounds)
    compile_s = time.perf_counter() - t0
    kernels = drive.kernel_names(compiled.as_text())
    del compiled, params
    prog = drive.set_up_calls(exp, rounds, traffic["set_up_calls"])
    setup_s = time.perf_counter() - T_START

    # ---- the measured window
    import jax.profiler

    profile_dir = os.path.join(WORK, "profile", workload)
    reduced = None
    if trace:
        shutil.rmtree(profile_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        with jax.profiler.trace(profile_dir, profiler_options=opts):
            with jax.profiler.TraceAnnotation("bench.window"):
                calls, window_s = drive.window(exp, rounds, seconds)
    else:
        calls, window_s = drive.window(exp, rounds, seconds)
    used = devs[:cell["chips"]]
    stats = [d.memory_stats() or {} for d in used]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    d_params = sum(int(a.size) for a in jax.tree.leaves(exp.params)) // n
    steps = cfg["method"]["local_steps"]
    del exp
    gc.collect()
    if trace:
        from bench import trace as tr

        reduced = tr.reduce(tr.load(profile_dir))

    # ---- the reference over the same first calls, then the comparison
    t0 = time.perf_counter()
    chips = cell["chips"]
    ref = reference_run(
        model, cfg, world,
        drive.make_params(model, cfg, seed, n,
                          sharding=node_sharding(chips)),
        rounds, traffic["set_up_calls"], chips=chips)
    ref["bytes"] = (traffic["wire_bytes_per_value"] * d_params
                    * world.num_directed_edges * rounds
                    * traffic["set_up_calls"])
    found = correct.gaps(prog, ref)
    ok, checks = correct.judge(found, correct.limits(workload,
                                                     spec["where"]))
    print(f"bench: set-up {setup_s:.2f} s (world and weights {world_s:.2f} "
          f"s, init {init_s:.2f} s, compile {compile_s:.2f} s), window "
          f"{window_s:.2f} s, {calls} calls, "
          f"reference {time.perf_counter() - t0:.2f} s; memory {stats}",
          file=sys.stderr)

    # ---- metrics
    meth = cfg["method"]
    used_test = (len(world.x_test) // meth["eval_batch"]) * meth["eval_batch"]
    ctx = {
        "e2e": {"node_steps_per_s": calls * rounds * n * steps / window_s,
                "peak_hbm_gib": peak / 2 ** 30, "setup_s": setup_s},
        "trace": reduced, "peaks": peaks, "chips": cell["chips"],
        "cfg": cfg, "traffic": traffic, "nodes": n,
        "params_per_node": d_params,
        "directed_edges": world.num_directed_edges,
        "calls_traced": calls, "rounds_traced": calls * rounds,
        "kernels": kernels,
        "flops_per_call": model.flops_per_call(cfg, n, rounds, 2, used_test),
        "setup": {"init_s": init_s, "compile_s": compile_s},
    }
    bench = spec["bench"]
    if trace:
        group = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    else:
        group = [m for m in bench["end_to_end"]
                 if workload in m.get("workloads", [workload])]
    units = {m["name"]: m["unit"] for m in group}
    result = {
        "correct": ok, "attempted": calls, "failed": 0,
        "metrics": _metrics([m["name"] for m in group], ctx, units, trace),
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs),
                   "memory_peak_bytes": peak},
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime's logs stay inside the checkout
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(WORK, "tpu_logs"))
    sys.path.insert(0, ROOT)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
