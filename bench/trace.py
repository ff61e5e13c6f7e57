"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

`load(profile_dir)` reads the newest `*.xplane.pb` under the directory with
`jax.profiler.ProfileData` and keeps three things:

  * the device ops of every accelerator plane (`/device:TPU:<k>`, line
    "XLA Ops"), as (name, start_ns, end_ns);
  * the host spans the benchmark writes with `TraceAnnotation` (names that
    start with `bench.`), which fix the window;
  * every host event, to say what the host was doing in a gap of the
    device.

`reduce(trace)` then gives, over the `bench.window` span: the busy time of
each device (the union of its op intervals), the self time of each op (its
time less that of the ops nested in it, as a loop holds its body; ops are
named by their HLO instruction, `fusion.12`), the ten ops with the most
self time, and the ten longest idle gaps, each named by the innermost host
event that covers its middle.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MIN_GAP_NS = 1000.0  # shorter stretches between ops are not idle gaps


def newest_xplane(profile_dir: str) -> str:
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {profile_dir}")
    return max(paths, key=os.path.getmtime)


def load(profile_dir: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(newest_xplane(profile_dir))
    devices, spans, host = {}, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, float(e.start_ns),
                                float(e.start_ns + e.duration_ns))
                               for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    ev = (e.name, float(e.start_ns),
                          float(e.start_ns + e.duration_ns))
                    host.append(ev)
                    if e.name.startswith("bench."):
                        spans.append(ev)
    return {"devices": devices, "spans": spans, "host": host}


def op_name(event_name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    m = re.match(r"%?([^\s=]+)", event_name)
    return m.group(1) if m else event_name


def self_times(ops, lo: float, hi: float):
    """{op name: ns} of each op's time in [lo, hi) less its nested ops'."""
    out = defaultdict(float)
    stack = []  # [name, end, clipped duration, time of direct children]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out[top[0]] += top[2] - top[3]
        d = max(0.0, min(e, hi) - max(s, lo))
        if stack:
            stack[-1][3] += d
        stack.append([op_name(name), e, d, 0.0])
    for top in stack:
        out[top[0]] += top[2] - top[3]
    return out


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of [start, end) intervals clipped to [lo, hi)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals
                       if e > lo and s < hi):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals, lo: float, hi: float):
    """[(start, end)] stretches of [lo, hi), a microsecond or longer, that
    no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e - s >= MIN_GAP_NS]


def _innermost(events, t: float) -> str:
    """Name of the shortest host event that covers time t."""
    best = None
    for name, s, e in events:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "no host event"


def window_bounds(trace: dict):
    spans = [(s, e) for name, s, e in trace["spans"] if name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy time, per-op self time, top ops and idle gaps in the window.

    `busy_s` and `op_s` are averaged over the devices that ran any op."""
    lo, hi = window_bounds(trace)
    busy, op_s = [], defaultdict(float)
    gaps = []
    active = {k: v for k, v in trace["devices"].items() if v}
    for name, ops in active.items():
        iv = [(s, e) for _, s, e in ops]
        busy.append(union_ns(iv, lo, hi))
        for op, t in self_times(ops, lo, hi).items():
            op_s[op] += t / len(active)
        gaps.extend(_gaps(iv, lo, hi))
    window_s = (hi - lo) * 1e-9
    busy_s = (sum(busy) / len(busy)) * 1e-9 if busy else 0.0
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "devices": len(active),
        "op_s": {k: v * 1e-9 for k, v in op_s.items()},
        "device_ops": [[k, v * 1e-9] for k, v in top_ops],
        "idle_gaps": [[_innermost(trace["host"], (s + e) / 2),
                       (e - s) * 1e-9] for s, e in longest],
    }


def kernel_seconds(reduced: dict, kernel: str) -> float:
    """Device self seconds of every op whose name contains `kernel`."""
    return sum(v for k, v in reduced["op_s"].items() if kernel in k)
