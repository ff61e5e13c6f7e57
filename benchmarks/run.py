"""Benchmark entry point: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Prints a ``name,us_per_call,derived`` CSV summary at the end (per-benchmark
detail printed as it runs).  --full uses paper-closer settings (3 datasets,
more rounds); the default is sized for this 2-core CPU container.
"""
from __future__ import annotations

import argparse
import time

from repro.utils.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--skip-sim", action="store_true",
                    help="skip the multi-minute simulation benches")
    args = ap.parse_args()
    enable_compile_cache()

    csv = [("name", "us_per_call", "derived")]

    def record(name, t0, derived):
        csv.append((name, f"{(time.time() - t0) * 1e6:.0f}", derived))

    # --- kernels (fast) -------------------------------------------------
    from benchmarks import bench_kernels

    t0 = time.time()
    rows = bench_kernels.run()
    record("kernels", t0, f"{len(rows)} shapes vs TPU roofline")

    # --- engine runner (scan-fused vs per-round loop) -------------------
    from benchmarks import bench_engine

    t0 = time.time()
    eng = bench_engine.run(verbose=False)
    record("engine_runner", t0,
           f"scan-fused {eng['fused_speedup_vmap']:.2f}x vs per-round loop")

    # --- node-axis scaling (dense vs sparse layout) ---------------------
    from benchmarks import bench_scale

    t0 = time.time()
    # the reduced lane runs the smoke sweep (scale_smoke artifact) so a
    # down-scaled pass never clobbers the committed BENCH_scale.json;
    # --full refreshes the real artifact + BENCH verdict.
    sc = bench_scale.run(smoke=not args.full, verbose=False)
    sparse_rows = [r for r in sc["rows"]
                   if r["layout"] == "sparse" and "rounds_per_sec" in r]
    top = max(sparse_rows, key=lambda r: r["nodes"])
    record("scale", t0,
           f"sparse n={top['nodes']} {top['rounds_per_sec']:.2f} rounds/s; "
           f"builder n={sc['builder']['nodes']} "
           f"{sc['builder']['wall_s']:.1f}s")

    # --- dynamics suite (time-varying topologies) -----------------------
    from benchmarks import bench_dynamics

    t0 = time.time()
    # the reduced lane runs as a smoke sweep (dynamics_smoke artifact) so a
    # down-scaled pass never clobbers the committed BENCH_dynamics.json;
    # --full refreshes the real artifact + BENCH verdict.
    dyn_rows = bench_dynamics.run(
        rounds=40 if args.full else 15,
        nodes=16 if args.full else 12,
        verbose=False, smoke=not args.full)
    drop = next(r for r in dyn_rows
                if r["world"] == "ba" and r["comm"] == "int8+adaptive"
                and r["process"].startswith("dropout"))
    record("dynamics_suite", t0,
           f"int8+adaptive dropout(0.2) dAcc={drop['acc_delta_vs_static']:+.3f} "
           f"bytes={drop['bytes_ratio_vs_static']:.2f}x vs static")

    # --- time-to-accuracy suite (event clock) ---------------------------
    from benchmarks import bench_time

    t0 = time.time()
    # the reduced lane runs as a smoke sweep (time_smoke artifact) so a
    # down-scaled pass never clobbers the committed BENCH_time.json;
    # --full refreshes the real artifact + BENCH verdict.
    time_rows = bench_time.run(
        rounds=40 if args.full else 10,
        nodes=16 if args.full else 8,
        verbose=False, smoke=not args.full)
    tbase = next(r for r in time_rows if r["world"] == "ba"
                 and r["config"] == "sync-fp32")
    tchal = next(r for r in time_rows if r["world"] == "ba"
                 and r["config"] == "deadline-int8"
                 and r["scenario"] == "hetero")
    record("time_suite", t0,
           f"sync {tbase['sim_time']:.0f}s vs deadline "
           f"{tchal['sim_time']:.0f}s simulated (ba, "
           f"dAcc={tchal['acc_mean'] - tbase['acc_mean']:+.3f})")

    # --- telemetry overhead + ledger/trace (repro.obs) ------------------
    from benchmarks import bench_obs

    t0 = time.time()
    # same smoke convention: the reduced lane writes obs_smoke only;
    # --full refreshes the obs_suite artifact behind BENCH_obs.json.
    if args.full:
        obs = bench_obs.run(verbose=False)
    else:
        obs = bench_obs.run(rounds=8, eval_every=4, verbose=False,
                            smoke=True)
    record("obs_telemetry", t0,
           f"all-channels overhead {obs['overhead_frac'] * 100:+.1f}% "
           f"(gate <=5%), trace bytes "
           f"{'exact' if obs['trace']['bytes_exact'] else 'MISMATCH'}")

    # --- comm table (paper §VI-A.3) ------------------------------------
    from benchmarks import bench_comm

    t0 = time.time()
    rows = bench_comm.run(verbose=False, with_frontier=False)
    ge = next(r for r in rows if r["method"] == "cfa-ge" and "mlp" in r["model"]
              and r["codec"] == "fp32")
    dd = next(r for r in rows if r["method"] == "decdiff+vt"
              and "mlp" in r["model"] and r["codec"] == "fp32")
    record("comm_table", t0,
           f"cfa-ge/decdiff+vt bytes ratio={ge['bytes_per_round']/dd['bytes_per_round']:.1f}x")

    # --- roofline over dry-run artifacts (deliverable g) ----------------
    from benchmarks import roofline

    t0 = time.time()
    recs = roofline.load()
    if recs:
        ok = sum(1 for r in recs if r.get("ok"))
        print(roofline.format_table(recs))
        record("roofline", t0, f"{ok}/{len(recs)} single-pod combos ok")
    else:
        record("roofline", t0, "no dryrun artifacts (run repro.launch.dryrun)")

    if not args.skip_sim:
        # --- Fig. 1 disruption ------------------------------------------
        from benchmarks import bench_disruption

        t0 = time.time()
        _, summary = bench_disruption.run(
            num_nodes=24 if args.full else 12,
            rounds=8 if args.full else 5,
            data_scale=0.06 if args.full else 0.03)
        record("fig1_disruption", t0,
               f"dechetero drop={summary['dechetero']:+.3f} "
               f"decdiff+vt drop={summary['decdiff+vt']:+.3f}")

        # --- Table II accuracy + Table IV char-time ---------------------
        from benchmarks import bench_accuracy, bench_char_time

        t0 = time.time()
        datasets = (("synth-mnist", "synth-fashion", "synth-emnist")
                    if args.full else ("synth-mnist",))
        res = bench_accuracy.run(
            datasets=datasets,
            rounds=150 if args.full else 110,
            num_nodes=30 if args.full else 16,
            data_scale=0.08 if args.full else 0.04)
        print(bench_accuracy.format_table(res))
        first = res[datasets[0]]
        record("table2_accuracy", t0,
               f"decdiff+vt={first['decdiff+vt']['acc_mean']:.3f} "
               f"dechetero={first['dechetero']['acc_mean']:.3f} "
           f"isol={first['isol']['acc_mean']:.3f}")

        t0 = time.time()
        ct = bench_char_time.characteristic_times(res)
        print(bench_char_time.format_table(ct))
        record("table4_char_time", t0, "from accuracy histories")

        # --- Table III ablation ------------------------------------------
        from benchmarks import bench_ablation

        t0 = time.time()
        ab = bench_ablation.run(
            rounds=150 if args.full else 110,
            num_nodes=30 if args.full else 16,
            data_scale=0.08 if args.full else 0.04)
        print(bench_ablation.format_table(ab))
        record("table3_ablation", t0,
               f"decdiff+vt - dechetero = "
               f"{100*(ab['decdiff+vt']['acc_mean']-ab['dechetero']['acc_mean']):+.2f}%pt")

        if args.full:
            # --- beyond-paper: topology sensitivity ----------------------
            from benchmarks import bench_topology

            t0 = time.time()
            rows = bench_topology.run(rounds=40)
            record("topology", t0, f"{len(rows)} (topology x method) cells")

    print()
    for row in csv:
        print(",".join(str(c) for c in row))


if __name__ == "__main__":
    main()
