"""Regenerate EXPERIMENTS.md from artifacts (dry-run, roofline, paper suite).

    PYTHONPATH=src python -m benchmarks.gen_report
"""
from __future__ import annotations

import json
import os
from typing import Optional

from benchmarks import roofline as rl
from benchmarks.common import load_results

ROOT = os.path.join(os.path.dirname(__file__), "..")


def repro_section() -> str:
    out = []
    acc = load_results("accuracy_table") or {}
    dis = load_results("disruption") or {}
    abl = load_results("ablation_table") or {}
    comm = load_results("comm_table") or []

    out.append("### Table II — final node-average accuracy (reduced rendition)\n")
    if acc:
        out.append("| dataset | GI | method | avg acc | ±std |")
        out.append("|---|---|---|---|---|")
        for ds, res in acc.items():
            gi = res.get("_world", {}).get("gini", 0)
            for m, r in res.items():
                if m.startswith("_"):
                    continue
                out.append(f"| {ds} | {gi:.2f} | {m} | {r['acc_mean']:.4f} | "
                           f"{r.get('acc_std', 0):.4f} |")
        out.append("")

        out.append("### Table IV — characteristic time (rounds to x% of centralized)\n")
        from benchmarks.bench_char_time import THRESHOLDS, characteristic_times
        ct = characteristic_times(acc)
        out.append("| dataset | method | 50% | 80% | 90% | 95% |")
        out.append("|---|---|---|---|---|---|")
        for ds, block in ct.items():
            for m, row in block["times"].items():
                cells = " | ".join("-" if row[t] is None else str(row[t])
                                   for t in THRESHOLDS)
                out.append(f"| {ds} | {m} | {cells} |")
        out.append("")

    if dis:
        out.append("### Fig. 1 — round-0 -> round-1 accuracy change "
                   "(positive = disruption)\n")
        out.append("| method | Δ accuracy |")
        out.append("|---|---|")
        for m, d in dis["round0_to_1_drop"].items():
            out.append(f"| {m} | {d:+.4f} |")
        out.append("")

    if abl:
        out.append("### Table III — ablation (CE/VT x DecAvg/DecDiff/CFA)\n")
        base = abl.get("dechetero", {}).get("acc_mean")
        out.append("| method | avg acc | gain vs DecHetero [%pt] |")
        out.append("|---|---|---|")
        for m, r in abl.items():
            if m.startswith("_"):
                continue
            gain = "" if base is None else f"{100 * (r['acc_mean'] - base):+.2f}"
            out.append(f"| {m} | {r['acc_mean']:.4f} | {gain} |")
        out.append("")

    if comm:
        out.append("### §VI-A.3 — communication bytes per round "
                   "(50-node ER p=.2)\n")
        out.append("| model | method | MB/round (fp32) |")
        out.append("|---|---|---|")
        for r in comm:
            if r.get("codec", "fp32") != "fp32":
                continue
            if r["method"] in ("isol", "fedavg", "cfa-ge", "decdiff+vt"):
                out.append(f"| {r['model']} | {r['method']} | "
                           f"{r['bytes_per_round'] / 1e6:.1f} |")
        out.append("")

    front = load_results("comm_frontier") or []
    if front:
        out.append("### Comm tentpole — accuracy-vs-bytes frontier "
                   "(8-node BA + ER smoke, DecDiff+VT)\n")
        out.append("Codec x trigger-policy sweep (fixed drift thresholds "
                   "and the per-edge adaptive controller); wire bytes are "
                   "the simulator's exact dynamic accounting "
                   "(event-triggered silence costs nothing).  Read it as: "
                   "how many bytes buy how much accuracy.\n")
        out.append("| world | codec | trigger | final acc | wire MB | "
                   "reduction | Δacc vs dense | trig frac |")
        out.append("|---|---|---|---|---|---|---|---|")
        from benchmarks.bench_comm import trigger_label

        for r in front:
            ratio = f" (r={r['topk_ratio']})" if r.get("topk_ratio") else ""
            if r.get("topk_momentum"):
                ratio += f" mom={r['topk_momentum']}"
            trig = trigger_label(r.get("policy", "fixed"), r["threshold"],
                                 r.get("target_trigger"))
            out.append(
                f"| {r.get('world', 'ba')} | {r['codec']}{ratio} | {trig} | "
                f"{r['acc_mean']:.4f} | {r['bytes_on_wire'] / 1e6:.2f} | "
                f"{r['reduction_vs_dense']:.1f}x | "
                f"{r['acc_delta_vs_dense']:+.4f} | {r['triggered_frac']:.2f} |")
        out.append("")
    return "\n".join(out)


def write_bench_comm() -> str:
    """Fold the comm artifacts into BENCH_comm.json: the static per-codec
    table, the accuracy-vs-bytes frontier (BA and ER worlds), and two
    acceptance verdicts — the PR-2 gate (some int8/top-k point with >= 2x
    fewer bytes within 1% of dense acc) and the PR-3 adaptive gate (some
    adaptive per-edge point within 1% of dense whose reduction is >= the
    best within-1% FIXED-threshold int8 reduction in the same world)."""
    table = load_results("comm_table") or []
    front = load_results("comm_frontier") or []
    if not front:
        # never clobber a committed BENCH_comm.json with an empty verdict
        # just because artifacts/ was cleaned; the frontier sweep
        # (bench_comm.frontier / bench_comm.run) is what refreshes it.
        print("comm_frontier artifact missing; BENCH_comm.json not rewritten")
        return None
    for r in front:  # tolerate pre-PR-3 artifacts
        r.setdefault("world", "ba")
        r.setdefault("policy", "fixed")
    dense = {
        w: next((r for r in front
                 if r["world"] == w and r["codec"] == "fp32"
                 and r["policy"] == "fixed" and r["threshold"] == 0.0), None)
        for w in {r["world"] for r in front}
    }

    def within_1pct(r):
        # at most 1% (relative) BELOW dense; better-than-dense passes
        d = dense.get(r["world"])
        return (d is not None and
                r["acc_delta_vs_dense"] >= -0.01 * max(d["acc_mean"], 1e-9))

    # the PR-2 gate keeps its original scope: the BA smoke world (an ER-only
    # pass must not mask a BA regression); the adaptive gate below is
    # per-world by construction.
    passing = [r for r in front
               if r["world"] == "ba" and r["codec"] in ("int8", "topk")
               and r["reduction_vs_dense"] >= 2.0 and within_1pct(r)]
    fixed_int8_bar = {
        w: max((r["reduction_vs_dense"] for r in front
                if r["world"] == w and r["codec"] == "int8"
                and r["policy"] == "fixed" and within_1pct(r)), default=None)
        for w in dense
    }
    adaptive_passing = [
        r for r in front
        if r["policy"] == "adaptive" and within_1pct(r)
        and fixed_int8_bar.get(r["world"]) is not None
        and r["reduction_vs_dense"] >= fixed_int8_bar[r["world"]]
    ]
    payload = {
        "dense_baseline": dense,
        "frontier": front,
        "acceptance": {
            "criterion": ">=2x bytes-on-wire reduction within 1% of dense "
                         "final accuracy (int8 or top-k, seeded BA smoke)",
            "passed": bool(passing),
            "passing_points": passing,
            "note": "fixed trigger_threshold > 0 points trade accuracy for "
                    "bytes on this short smoke run (see frontier deltas); "
                    "the within-1% bar is cleared by the always-send int8 "
                    "point and by the adaptive per-edge points (below). "
                    "The trigger's own guarantee (>=2x at bounded loss) is "
                    "pinned separately in tests/test_system.py.",
        },
        "adaptive_acceptance": {
            "criterion": "some adaptive per-edge point within 1% of dense "
                         "with bytes reduction >= the best within-1% "
                         "fixed-threshold int8 reduction (per world)",
            "fixed_int8_reduction_bar": fixed_int8_bar,
            "passed": bool(adaptive_passing),
            "passing_points": adaptive_passing,
        },
        "static_table": table,
    }
    path = os.path.join(ROOT, "BENCH_comm.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    return path


def write_bench_engine() -> str:
    """Fold the engine-runner sweep into BENCH_engine.json: rounds/sec per
    (backend, schedule mode) on the 16-node BA smoke world, plus the
    acceptance verdict — the scan-fused schedule must reach >= 2x the
    per-round Python loop's rounds/sec on the vmap backend (the repo's
    first runner-layer perf gate; see benchmarks/bench_engine.py)."""
    res = load_results("engine_runner") or {}
    if not res:
        print("engine_runner artifact missing; BENCH_engine.json not "
              "rewritten (run python -m benchmarks.bench_engine)")
        return None
    speedup = res.get("fused_speedup_vmap", 0.0)
    wire_ratio = res.get("encoded_over_decoded_shardmap")
    payload = {
        "world": res.get("world", {}),
        "rows": res.get("rows", []),
        "wire_rows": res.get("wire_rows", []),
        "acceptance": {
            "criterion": "scan-fused schedule >= 2x rounds/sec vs the "
                         "per-round Python loop (vmap backend, 16-node BA "
                         "smoke world)",
            "fused_speedup_vmap": speedup,
            "passed": bool(speedup >= 2.0),
            "note": "modes are bit-identical in math (pinned by "
                    "tests/test_engine.py); this measures pure execution "
                    "strategy: one lax.scan program dispatched once vs one "
                    "XLA dispatch per round plus jitted eval calls.",
        },
        "wire_acceptance": {
            "criterion": "shard_map encoded-payload exchange (the default "
                         "wire) >= 0.9x the decoded-rows oracle's "
                         "rounds/sec (int8 event-triggered transport; 0.9 "
                         "absorbs shared-CPU timing noise — the encoded "
                         "wire also ships ~4x fewer bytes across the pod "
                         "axis)",
            "encoded_over_decoded_shardmap": wire_ratio,
            "passed": None if wire_ratio is None else bool(wire_ratio >= 0.9),
            "note": "wires are informationally identical (one exchange "
                    "step is bitwise equal across wires; pinned by "
                    "tests/test_engine.py); null when the bench host had "
                    "no pod axis.",
        },
    }
    path = os.path.join(ROOT, "BENCH_engine.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    return path


def write_bench_dynamics() -> Optional[str]:
    """Fold the dynamics suite into BENCH_dynamics.json: accuracy/bytes for
    dense fp32 and int8+adaptive DecDiff+VT under every catalog
    GraphProcess vs the static baseline (BA and ER 16-node smoke worlds),
    plus the acceptance verdict — int8+adaptive under i.i.d. edge dropout
    (p=0.2) must stay within 3% (relative) of its OWN static-graph final
    accuracy on the BA world (see benchmarks/bench_dynamics.py)."""
    rows = load_results("dynamics_suite") or []
    if not rows:
        # never clobber a committed BENCH_dynamics.json just because
        # artifacts/ was cleaned; the full (non --smoke) sweep refreshes it.
        print("dynamics_suite artifact missing; BENCH_dynamics.json not "
              "rewritten (run python -m benchmarks.bench_dynamics)")
        return None
    statics = {(r["world"], r["comm"]): r for r in rows
               if r["process"] == "static"}
    accept_row = next(
        (r for r in rows
         if r["world"] == "ba" and r["comm"] == "int8+adaptive"
         and r["process"].startswith("dropout")), None)
    passed = False
    if accept_row is not None:
        base = statics.get(("ba", "int8+adaptive"))
        passed = (base is not None and
                  accept_row["acc_delta_vs_static"]
                  >= -0.03 * max(base["acc_mean"], 1e-9))
    payload = {
        "static_baselines": {f"{w}/{c}": r for (w, c), r in statics.items()},
        "rows": rows,
        "acceptance": {
            "criterion": "int8+adaptive under i.i.d. edge dropout (p=0.2) "
                         "within 3% (relative) of its static-graph final "
                         "accuracy (16-node BA smoke world, DecDiff+VT)",
            "passed": bool(passed),
            "point": accept_row,
            "note": "bytes are accounted on live edges only, so every "
                    "dynamic point also ships FEWER bytes than its static "
                    "baseline (see bytes_ratio_vs_static); the gate is "
                    "about accuracy surviving the missing edges.",
        },
    }
    path = os.path.join(ROOT, "BENCH_dynamics.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    return path


def write_bench_time() -> Optional[str]:
    """Fold the time-to-accuracy suite into BENCH_time.json: the event
    clock's frontier — per-edge adaptive int8 under `Schedule(deadline=...)`
    vs the synchronous fp32 baseline on the 16-node BA and ER smoke worlds
    under heterogeneous compute and links — plus the straggler scenario,
    and the acceptance verdicts: (a) the challenger reaches 90% of the
    baseline's own final accuracy in STRICTLY less simulated time on both
    worlds, (b) with 10% of nodes 8x slower the deadline run stays within
    3% (relative) of the homogeneous-clock run (see
    benchmarks/bench_time.py)."""
    rows = load_results("time_suite") or []
    if not rows:
        # never clobber a committed BENCH_time.json just because
        # artifacts/ was cleaned; the full (non --smoke) sweep refreshes it.
        print("time_suite artifact missing; BENCH_time.json not "
              "rewritten (run python -m benchmarks.bench_time)")
        return None
    hetero = [r for r in rows if r["scenario"] == "hetero"]
    frontier = []
    for wname in sorted({r["world"] for r in hetero}):
        base = next((r for r in hetero if r["world"] == wname
                     and r["config"] == "sync-fp32"), None)
        chal = next((r for r in hetero if r["world"] == wname
                     and r["config"] == "deadline-int8"), None)
        if base is None or chal is None:
            continue
        bt, ct = base.get("time_to_target"), chal.get("time_to_target")
        frontier.append({
            "world": wname, "target_acc": base.get("target_acc"),
            "sync_time_to_target": bt, "deadline_time_to_target": ct,
            "speedup": (bt / ct) if bt and ct else None,
            "passed": bool(bt is not None and ct is not None and ct < bt),
        })
    frontier_passed = bool(frontier) and all(f["passed"] for f in frontier)
    homog = next((r for r in rows if r["scenario"] == "homogeneous"), None)
    strag = next((r for r in rows
                  if r["scenario"].startswith("straggler")), None)
    strag_passed = bool(
        homog and strag
        and abs(strag["acc_mean"] - homog["acc_mean"])
        <= 0.03 * max(homog["acc_mean"], 1e-9))
    payload = {
        "rows": rows,
        "frontier": frontier,
        "acceptance": {
            "criterion": "event-triggered per-edge adaptive int8 under a "
                         "deadline reaches 90% of the synchronous fp32 "
                         "baseline's own final accuracy in strictly less "
                         "simulated time on BA and ER (16-node smoke "
                         "worlds, DecDiff+VT, lognormal compute + links)",
            "passed": frontier_passed,
            "straggler": {
                "criterion": "with 10% of nodes 8x slower, the deadline "
                             "run's final accuracy stays within 3% "
                             "(relative) of the homogeneous-clock run "
                             "(same deadline, same links)",
                "passed": strag_passed,
                "homogeneous_acc": homog and homog["acc_mean"],
                "straggler_acc": strag and strag["acc_mean"],
            },
            "note": "simulated time is the event clock's accounting: the "
                    "sync baseline pays the realized makespan (slowest "
                    "node + slowest live link, priced from the codec's "
                    "exact bytes on wire) every round, while the deadline "
                    "run pays exactly one tick and lets late payloads "
                    "fall into the stale silence path.",
        },
    }
    path = os.path.join(ROOT, "BENCH_time.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    return path


def write_bench_scale() -> Optional[str]:
    """Fold the node-axis scaling sweep into BENCH_scale.json: rounds/sec
    per (N, layout) on the tiny-MLP BA gossip world, the 10^5-receiver
    kernel tier, the 10^6-node builder tier, the dynamics tier
    (int8+adaptive per-edge transport under 20% dropout on the sparse
    engine), and the acceptance verdicts — the sparse layout must complete
    an engine round at >= 10^4 nodes at a node count where the dense layout
    is skipped (projected memory wall) or >= 5x slower, and the dynamics
    tier must run there with the realized live fraction at its 1 - p
    stationary value (see benchmarks/bench_scale.py)."""
    res = load_results("scale_sweep") or {}
    if not res:
        # never clobber a committed BENCH_scale.json just because
        # artifacts/ was cleaned; the full (non --smoke) sweep refreshes it.
        print("scale_sweep artifact missing; BENCH_scale.json not "
              "rewritten (run python -m benchmarks.bench_scale)")
        return None
    rows = res.get("rows", [])
    by_n = {}
    for r in rows:
        by_n.setdefault(r["nodes"], {})[r["layout"]] = r
    passing = []
    for n, pair in sorted(by_n.items()):
        dn, sp = pair.get("dense"), pair.get("sparse")
        if n < 10_000 or sp is None or "rounds_per_sec" not in sp:
            continue
        dense_walled = (dn is None or dn.get("skipped") is not None
                        or (dn.get("rounds_per_sec", 0.0)
                            <= sp["rounds_per_sec"] / 5.0))
        if dense_walled:
            passing.append({"nodes": n,
                            "sparse_rounds_per_sec": sp["rounds_per_sec"],
                            "dense": (dn or {}).get("skipped",
                                                    "not swept")
                            if dn is None or "rounds_per_sec" not in dn
                            else f"{dn['rounds_per_sec']:.3f} rounds/s"})
    dyn = res.get("dynamics")
    dyn_passed = bool(
        dyn and dyn.get("nodes", 0) >= 10_000
        and dyn.get("rounds_per_sec", 0.0) > 0.0
        and abs(dyn.get("live_frac_mean", 0.0)
                - (1.0 - dyn.get("dropout_p", 0.2))) < 0.02
        and 0.0 < dyn.get("trig_frac_mean", 0.0) <= 1.0)
    payload = {
        "world": res.get("world", {}),
        "dense_bytes_budget": res.get("dense_bytes_budget"),
        "rows": rows,
        "kernel": res.get("kernel"),
        "builder": res.get("builder"),
        "dynamics": dyn,
        "acceptance": {
            "criterion": "sparse layout completes engine rounds at >= 10^4 "
                         "nodes where dense is memory-walled (projected "
                         "block over budget) or >= 5x slower",
            "passed": bool(passing),
            "passing_points": passing,
            "dynamics": {
                "criterion": "int8+adaptive per-edge transport under 20% "
                             "i.i.d. edge dropout completes at >= 10^4 "
                             "nodes on the sparse engine, with the "
                             "realized live fraction within 0.02 of the "
                             "1 - p stationary value and a sane triggered "
                             "fraction",
                "passed": dyn_passed,
            },
            "note": "dense and sparse are bit-identical where both run — "
                    "methods x transports x dynamics x backends, pinned in "
                    "tests/test_sparse_parity.py; this artifact records "
                    "what the sparse layout buys past the dense wall.",
        },
    }
    path = os.path.join(ROOT, "BENCH_scale.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    return path


def write_bench_obs() -> Optional[str]:
    """Fold the telemetry bench into BENCH_obs.json: the all-channels
    overhead pair on the 16-node fused schedule, the ledger/trace
    validation results, and the acceptance verdicts — all-channels
    rounds/sec within 5% of telemetry-off, and the exported Chrome
    trace's per-edge transfer-span bytes summing EXACTLY to the run's
    bytes_on_wire (see benchmarks/bench_obs.py)."""
    res = load_results("obs_suite") or {}
    if not res:
        # never clobber a committed BENCH_obs.json just because
        # artifacts/ was cleaned; the full (non --smoke) run refreshes it.
        print("obs_suite artifact missing; BENCH_obs.json not "
              "rewritten (run python -m benchmarks.bench_obs)")
        return None
    payload = {
        "world": res.get("world"),
        "rows": res.get("rows", []),
        "ledger": res.get("ledger"),
        "trace": res.get("trace"),
        "dispersion": res.get("dispersion"),
        "acceptance": {
            "criterion": "with EVERY telemetry channel accumulating in "
                         "the scan carry (steps, compute seconds, "
                         "accuracy, trigger counts, exact bytes, "
                         "staleness, landing latency, consensus, drift), "
                         "the fused schedule's rounds/sec stays within "
                         "5% of telemetry=None on the 16-node BA world",
            "overhead_frac": res.get("overhead_frac"),
            "passed": bool(res.get("overhead_passed")),
            "trace": {
                "criterion": "the Chrome-trace export's per-edge "
                             "transfer spans carry exact payload bytes "
                             "that sum to RoundMetrics.bytes_on_wire",
                "passed": bool(res.get("trace", {}).get("bytes_exact")),
            },
        },
    }
    path = os.path.join(ROOT, "BENCH_obs.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    return path


def obs_section() -> str:
    """The observability tentpole's report section, built from the RUN
    LEDGER the bench emitted (not from in-memory results): per-node
    accuracy dispersion and the per-edge byte distribution at the final
    eval round — the distributional surface the node-mean tables hide."""
    res = load_results("obs_suite") or {}
    if not res:
        return ""
    from benchmarks.common import ART_DIR
    from repro.obs import read_ledger

    ledger_path = os.path.join(ART_DIR, res["ledger"]["path"])
    if not os.path.exists(ledger_path):
        return ""
    manifest, rounds, summaries = read_ledger(ledger_path)
    last = rounds[-1]
    detail = {k: [float(x) for x in v]
              for k, v in last.get("detail", {}).items()}
    out = ["### Observability tentpole — telemetry channels "
           f"(16-node BA, {manifest['method']}, all channels)\n",
           "Read back from the schema-validated run ledger "
           f"(`{res['ledger']['path']}`: {res['ledger']['counts']}); "
           "per-edge channels are in the canonical (dst, src) directed-"
           "edge order.  BENCH_obs.json carries the ≤5% overhead and "
           "exact-trace-bytes acceptance gates "
           f"(overhead {res['overhead_frac'] * 100:+.1f}%).\n"]

    def pct(vals, q):
        v = sorted(vals)
        return v[min(len(v) - 1, int(q / 100 * len(v)))]

    acc = last["acc_per_node"]
    out.append("| channel | min | p50 | p95 | max |")
    out.append("|---|---|---|---|---|")
    out.append(f"| node accuracy | {min(acc):.4f} | {pct(acc, 50):.4f} | "
               f"{pct(acc, 95):.4f} | {max(acc):.4f} |")
    for name, scale, fmt in (("node_steps", 1, ".0f"),
                             ("node_compute", 1, ".1f"),
                             ("edge_bytes", 1e6, ".2f"),
                             ("edge_trigger", 1, ".0f"),
                             ("edge_staleness", 1, ".0f"),
                             ("drift", 1, ".3f")):
        if name not in detail:
            continue
        v = [x / scale for x in detail[name]]
        label = name + (" (MB)" if scale == 1e6 else "")
        out.append(f"| {label} | {min(v):{fmt}} | {pct(v, 50):{fmt}} | "
                   f"{pct(v, 95):{fmt}} | {max(v):{fmt}} |")
    if summaries:
        s = summaries[-1]
        out.append("")
        out.append(f"Ledger summary: {s['rounds_per_sec']:.2f} rounds/s "
                   f"wall ({s['wall_s']:.1f}s"
                   + (f", cold compile {s['compile_s']:.1f}s"
                      if s.get("cold_compile") else "") + ").")
    out.append("")
    return "\n".join(out)


def time_section() -> str:
    rows = load_results("time_suite") or []
    if not rows:
        return ""
    out = ["### Event-clock tentpole — time-to-accuracy "
           "(16-node BA + ER smoke, DecDiff+VT)\n",
           "The clock prices every round in simulated seconds (lognormal "
           "per-node step times, lognormal per-edge latency/bandwidth over "
           "the codec's exact bytes on wire).  `t@target` is the first "
           "evaluated sim_time reaching 90% of the synchronous baseline's "
           "own final accuracy.  BENCH_time.json carries the frontier and "
           "straggler acceptance gates.\n",
           "| world | config | scenario | final acc | sim time (s) | "
           "t@target (s) | arrived frac | wire MB |",
           "|---|---|---|---|---|---|---|---|"]
    for r in rows:
        ttt = r.get("time_to_target")
        out.append(
            f"| {r['world']} | {r['config']} | {r['scenario']} | "
            f"{r['acc_mean']:.4f} | {r['sim_time']:.1f} | "
            f"{'-' if ttt is None else f'{ttt:.1f}'} | "
            f"{r['arrived_frac']:.2f} | {r['bytes_on_wire'] / 1e6:.2f} |")
    out.append("")
    return "\n".join(out)


def dynamics_section() -> str:
    rows = load_results("dynamics_suite") or []
    if not rows:
        return ""
    out = ["### Dynamics tentpole — time-varying topologies "
           "(16-node BA + ER smoke, DecDiff+VT)\n",
           "Every `repro.dynamics.GraphProcess` vs the static baseline, "
           "dense fp32 and the production int8+adaptive transport.  Bytes "
           "are exact live-edge accounting (a non-existent link costs "
           "nothing); `Δacc` is against the SAME transport on the static "
           "graph.  BENCH_dynamics.json carries the within-3% dropout "
           "acceptance gate.\n",
           "| world | process | comm | final acc | Δacc vs static | "
           "wire MB | bytes vs static | live frac | trig frac |",
           "|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        out.append(
            f"| {r['world']} | {r['process']} | {r['comm']} | "
            f"{r['acc_mean']:.4f} | {r['acc_delta_vs_static']:+.4f} | "
            f"{r['bytes_on_wire'] / 1e6:.2f} | "
            f"{r['bytes_ratio_vs_static']:.2f}x | "
            f"{r['live_edge_frac']:.2f} | {r['triggered_frac']:.2f} |")
    out.append("")
    return "\n".join(out)


def engine_section() -> str:
    res = load_results("engine_runner") or {}
    if not res:
        return ""
    out = ["### Engine runner — scan-fused schedule vs per-round loop "
           "(16-node BA smoke, DecDiff+VT)\n",
           "Same math bit-for-bit (tests/test_engine.py); only the "
           "execution strategy differs.  BENCH_engine.json carries the "
           ">= 2x acceptance gate.\n",
           "| backend | schedule | rounds/s | timed wall s | compile+first s |",
           "|---|---|---|---|---|"]
    for r in res.get("rows", []):
        out.append(f"| {r['backend']} | {r['mode']} | "
                   f"{r['rounds_per_sec']:.1f} | {r['wall_s']:.2f} | "
                   f"{r['compile_and_first_run_s']:.2f} |")
    out.append("")
    out.append(f"* scan-fused speedup (vmap): "
               f"**{res.get('fused_speedup_vmap', 0.0):.2f}x**")
    out.append("")
    return "\n".join(out)


def dryrun_section() -> str:
    out = []
    for mesh in ("single", "multi"):
        recs = rl.load(mesh=mesh)
        ok = sum(1 for r in recs if r.get("ok"))
        out.append(f"* **{mesh}-pod mesh**: {ok}/{len(recs)} combinations "
                   f"lower+compile OK"
                   + ("" if ok == len(recs) else "  <-- FAILURES, see artifacts"))
    out.append("")
    out.append("Multi-pod status per combo (compile time, per-chip terms in "
               "artifacts/dryrun/*__multi.json):")
    out.append("")
    out.append("| arch | train_4k | prefill_32k | decode_32k | long_500k |")
    out.append("|---|---|---|---|---|")
    recs = {(r["arch"], r["shape"]): r for r in rl.load(mesh="multi")}
    archs = sorted({a for a, _ in recs})
    for a in archs:
        cells = []
        for sh in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
            r = recs.get((a, sh))
            cells.append("OK" if r and r.get("ok") else "FAIL")
        out.append(f"| {a} | " + " | ".join(cells) + " |")
    return "\n".join(out)


def roofline_section() -> str:
    recs = rl.load(mesh="single")
    out = [rl.format_table(recs), ""]
    doms = rl.summarize(recs)
    for dom, combos in sorted(doms.items()):
        out.append(f"* **{dom}-bound** ({len(combos)}): {', '.join(combos)}")
        out.append(f"  * lever: {rl.LEVERS[dom]}")
    out.append("")
    out.append(f"* hillclimb picks: {rl.pick_hillclimb_candidates(recs)}")
    return "\n".join(out)


PERF_LOG = r"""
Three pairs (picked from the baseline table): **mixtral-8x7b/train_4k**
(most collective-bound), **arctic-480b/train_4k** (worst roofline fraction),
**qwen3-32b/train_4k** incl. its multi-pod DFL round (most representative of
the paper's technique — the DecDiff pod-gossip runs in this step).  All
numbers are per-chip seconds/step from the calibrated dry-run
(artifacts/perf/*.json); variants via `dryrun.py --variant`.

### mixtral-8x7b / train_4k  (baseline C 2.11 / M 24.98 / **Coll 30.85**)

| # | hypothesis | change | result (C/M/Coll s) | verdict |
|---|---|---|---|---|
| 1 | activation psums stem from FSDP weight sharding; forcing use-site weight gather (ZeRO-3 constraint) will trade 45 GB of activation all-reduce for ~0.8 GB of weight all-gather | `zero3_gather` flag: re-constrain per-layer weight slices to model-only inside the scan | 16.62 / 64.55 / 72.59 | **REFUTED** — GSPMD resolved the conflicting constraint by replicating compute (8× flops). Reverted. |
| 2 | the 9.4 GB fp32 per-layer all-reduce is the MoE global-capacity buffer crossing the batch sharding; batch-local dispatch keeps tokens on their shard | `moe_dispatch="batch_local"` — first as vmap (buffers replicated: only −20%), then explicit batch dim + constraints | 2.11 / 21.18 / **17.89** | **CONFIRMED** — collective −42%, memory −15%. vmap lesson: per-partition HLO shapes showed local B=256 (replicated) until the batch dim was explicit. |
| 3 | fp32 attention probs are the largest remaining buffer; casting to bf16 before the combine halves that traffic | `attn_probs_bf16` | 2.11 / 21.35 / 17.89 | **REFUTED** — no change; scores/softmax stay fp32 and the cast adds a conversion pass. |
| 4 | seq-sharding the scan carry removes the residual psum chain | `moelocal+seqshard` | 2.10 / 18.73 / 16.33 | **CONFIRMED (small)** — final: collective −47%, memory −25% vs baseline. |

### arctic-480b / train_4k  (baseline C 3.58 / **M 31.59** / Coll 27.47)

| # | hypothesis | change | result | verdict |
|---|---|---|---|---|
| 1 | mixtral's batch-local dispatch transfers | `moelocal` | 5.23 / 48.77 / 32.37 | **REFUTED** — the tradeoff flips: arctic's 13.4 B params/layer of expert weights make the forced weight-gather (26.8 GB/layer) far worse than the activation psum. Expert count changes the optimum. |
| 2 | true expert parallelism (E=128 % 16 == 0): experts sharded over model, tokens all-to-all | `expertpar` (E-dim sharding rule + buffer constraints) | 5.22 / 41.78 / 25.00 | **REFUTED overall** — collective −9% but memory +32% (fp32 dispatch buffers + per-row capacity rounding). The baseline "TP-inside-experts" never moves weights and is already decent. |
| 3 | per-layer saved residual dominates; seq-shard the carry | `seqshard` | **2.44 / 24.63 / 22.39** | **CONFIRMED** — all three terms down (compute −32%, memory −22%, collective −18%); bytes/device 164 -> 92 GB. |

### qwen3-32b / train_4k + multi-pod DFL  (baseline single C 4.58 / **M 30.44** / Coll 10.02; multi C 2.27 / M 10.18 / Coll 4.81)

| # | hypothesis | change | result | verdict |
|---|---|---|---|---|
| 1 | the [16,4096,5120] bf16 carry saved per layer (×64) is the memory wall; seq-sharding it over model removes both the capacity and the psum chain | `seqshard` (single-pod) | 4.54 / **15.50 / 1.08** | **CONFIRMED, biggest single win** — memory −49%, collective −89% (all-reduce 475 -> 37 GB/chip), bytes/device 131.6 -> 28.4 GB. |
| 2 | same for the multi-pod DFL round | `seqshard` (multi) | 2.27 / 9.17 / 4.76 | **PARTIAL** — only −10% memory; the vmapped round keeps its activation psums. sdy dumps show the constraints ARE correctly pod-prefixed (verified `spmd_axis_name`, now enabled) — GSPMD chooses a different global solution when the gossip einsum consumes the stacked params. Open item. |
| 3 | manual-pod shard_map round (explicit adjacency-masked ppermute ring per DESIGN.md §3) sidesteps GSPMD's choice | `build_dfl_round_shardmap` | — | **BLOCKED** — XLA SPMD partitioner CHECK failure (spmd_partitioner_util.cc:504) on the (2,16,16) partial-auto mesh; implementation kept (works on small meshes), documented as toolchain-blocked. |
| 4 | bf16 gossip halves the paper's exchange volume | `gossipbf16` | no measurable change | **CONFIRMED-IRRELEVANT** — napkin + measurement agree: DecDiff gossip volume is params/chip ≈ 0.25 GB ≈ 5 ms vs a 4.8 s round. At pod scale the paper's "parameters-only" exchange is already negligible; local training dominates. This *quantifies* the paper's communication-efficiency claim on real hardware. |

**Stopping:** mixtral iterations 3-4 and arctic 2-3 brought <5%-per-change on
their dominant terms after the confirmed wins; remaining headroom is in the
`bytes accessed` proxy (fp32 softmax/score paths) and the multi-pod DFL psum
question above.

**Paper-faithful vs beyond-paper summary** (dominant-term seconds):

| pair | baseline (faithful) | best variant | Δ |
|---|---|---|---|
| mixtral-8x7b/train_4k | Coll 30.85 | Coll 16.33 (moelocal+seqshard) | **−47%** |
| arctic-480b/train_4k | Mem 31.59 | Mem 24.63 (seqshard) | **−22%** |
| qwen3-32b/train_4k | Mem 30.44 | Mem 15.50 (seqshard) | **−49%** |
"""


def main():
    sections = []
    sections.append("""# EXPERIMENTS

All results produced inside this (CPU-only, offline) container.  Real
datasets are unavailable -> synthetic stand-ins (DESIGN.md §1, data gate);
accuracy numbers are NOT the paper's absolute numbers — the claims validated
are the paper's ordering/qualitative claims.  TPU numbers are *derived*
(dry-run compile + v5e constants: 197 TF bf16, 819 GB/s HBM, 50 GB/s/link
ICI), not measured.

Contents: §Repro · §Dry-run · §Roofline · §Perf.

---

## §Repro — validating the paper's claims

Reduced rendition of paper §V (ER graph, truncated-Zipf α=1.26 non-IID,
per-node random init, SGD+momentum; 150 rounds x 30 nodes on synth-mnist,
80 x 16 on the CNN datasets; 1 replica — CPU budget).  Claim scoreboard:

| claim | paper artifact | verdict |
|---|---|---|
| C1 round-1 disruption hits DecHetero only | Fig. 1 | **confirmed** — DecHetero is the only method whose accuracy drops after the first aggregation (see Fig.1 table below) |
| C2 DecDiff+VT > DecHetero, CFA; ≳ CFA-GE, FedAvg | Table II | **confirmed** — see Table II below (DecDiff+VT tops every decentralized baseline and FedAvg) |
| C3 ablation: +VT adds over DecDiff/DecAvg alone | Table III | **confirmed for VT** (+6 %pt over DecHetero); DecDiff-alone is mixed on the synthetic task — consistent with the paper's own EMNIST row (−0.87 %pt). Beyond-paper rows show VT lifting every aggregator. |
| C4 DecDiff+VT fastest to relative-accuracy thresholds | Table IV | **confirmed at 90/95%** (see Table IV) |
| C5 comms: parameters only; CFA-GE ships 4x | §VI-A.3 | **confirmed** — exact accounting, 4.0x (comm table) |
| C6 less overfitting / tighter node spread | Fig. 5/6 | **confirmed** — DecDiff+VT final node-accuracy σ is the smallest among decentralized methods (Table II ±std) |

Note: on the synthetic datasets DecDiff+VT can exceed the CE-trained
centralized benchmark — the virtual teacher acts as a strong label-smoothing
regularizer against the generator's noise.  This does not occur in the
paper's real-data setting and we do not claim it; the validated statement is
the ORDERING among methods.
""")
    sections.append(repro_section())
    eng = engine_section()
    if eng:
        sections.append(eng)
    dyn = dynamics_section()
    if dyn:
        sections.append(dyn)
    tim = time_section()
    if tim:
        sections.append(tim)
    obs = obs_section()
    if obs:
        sections.append(obs)
    sections.append("""
## §Dry-run — (10 archs × 4 shapes) × (single-pod 16x16, multi-pod 2x16x16)

`PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both` — every
combination must `.lower().compile()`.  Steps per shape: train_4k ->
train_step (single) / DFL round with DecDiff pod-gossip (multi); prefill_32k
-> forward; decode shapes -> serve_step (1 token vs KV cache; long_500k uses
the sub-quadratic path per DESIGN.md §4).

**Methodology notes (each verified, see memory/dryrun-calibration-findings):**
1. XLA's HloCostAnalysis counts `lax.scan` bodies ONCE — all roofline terms
   come from calibration compiles (1/2 layers, scans unrolled, chunk grids
   enlarged) extrapolated linearly; 3-point fit for the zamba2 hybrid.
2. cost_analysis is per-partition; memory_analysis per-device; collective
   bytes parsed from post-SPMD HLO (result-shape ÷/× group size).
3. `bytes accessed` double-counts producer/consumer pairs — treat memory
   terms as an upper bound (~2x), comparable across combos.
4. The per-device `temp` from the CPU backend includes fp32 staging XLA:TPU
   would fuse; `fits 16GB = NO` rows are upper-bound capacity flags, with
   the §Perf seqshard variant the worst offenders drop 2-5x.
""")
    sections.append(dryrun_section())
    sections.append("""
## §Roofline — per (arch × shape), single-pod, per chip per step
""")
    sections.append(roofline_section())
    sections.append("""
## §Perf — hypothesis → change → measure → validate
""")
    sections.append(PERF_LOG)

    path = os.path.join(ROOT, "EXPERIMENTS.md")
    with open(path, "w") as f:
        f.write("\n".join(sections))
    print("wrote", path)
    for p in (write_bench_comm(), write_bench_engine(),
              write_bench_dynamics(), write_bench_time(),
              write_bench_obs()):
        if p:
            print("wrote", p)


if __name__ == "__main__":
    main()
