"""Read the program's own names out of a traced run: the named scope of
each device op, and the program's compile counters.

The program wraps the phases of its round in five `jax.named_scope`s.  A
scope is part of each op's HLO `op_name` metadata, which the TPU profiler
keeps as the `tf_op` stat of the op's event metadata; an op belongs to the
innermost `dfl.*` scope in it (`dfl.reduce` nests inside
`dfl.aggregate`).  The TPU compiler drops the op_name of ops it creates
itself, such as the dynamic-update-slice chains that build a concatenated
or gathered panel, and the profiler then files them under the enclosing
loop (`jit(program)/while`).  So an op with no scope of its own takes its
consumers' scope, when it has consumers and they all have the same one;
an op left without one is `other`.  Consumers come from the op's HLO text
(the event metadata's name), which names its operands.  The names are
spelled here and not imported from the program, so a program that
renames a scope makes the metrics read nothing rather than move.

`jax.profiler.ProfileData` does not show event-metadata stats, so
`op_metadata` reads them from the XSpace protobuf directly (only the
device planes' metadata tables; the timelines are skipped).

The readers are lazy and keep what they read in `ctx` (`ctx["scopes"]`,
`ctx["program"]`), so the metrics of one run read the profile once;
tests hand them in.  On a program without the scopes or counters every
reader returns None.

Temporary: `bench/run.py` does not pass the readers the run's profile
path or the counters around its compile, so `_this_run` finds the profile
again (the newest one whose window matches), `window_wall` re-reads it,
and `_read_program` takes `lower_s`/`load_s` from the process's
cumulative `span_table()`, which is right while `run.py` compiles once.
The `benchmark` change that lets `run.py` fill `ctx["scopes"]` and
`ctx["program"]` itself (PERF.md §7) deletes all three.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

from bench.trace import WINDOW_SPAN, op_name

SCOPES = ("dfl.train", "dfl.exchange", "dfl.reduce", "dfl.aggregate",
          "dfl.eval")
OTHER = "other"
_SCOPE_RE = re.compile(r"(?:^|[/(])(" + "|".join(
    re.escape(s) for s in SCOPES) + r")(?=$|[/):])")

_REF_RE = re.compile(r"%([\w.\-]+)")

HERE = os.path.dirname(os.path.abspath(__file__))
PROFILES = os.path.join(os.path.dirname(HERE), ".bench_work", "profile")


def scope_of(meta) -> str:
    """The innermost `dfl.*` scope of an HLO `op_name` string (None if the
    op has none), or `other`."""
    found = _SCOPE_RE.findall(meta or "")
    return found[-1] if found else OTHER


def op_scopes(ops) -> dict:
    """{HLO instruction name: its scope} from {instruction name: (op_name
    or None, names its HLO text refers to)}: the innermost scope of its own
    op_name, else the one scope all its consumers have."""
    scope = {op: scope_of(meta) for op, (meta, _) in ops.items()}
    users = defaultdict(set)
    for op, (_, refs) in ops.items():
        for ref in refs:
            if ref in ops and ref != op:
                users[ref].add(op)
    changed = True
    while changed:
        changed = False
        for op in ops:
            if scope[op] != OTHER or not users[op]:
                continue
            found = {scope[u] for u in users[op]}
            if len(found) == 1 and OTHER not in found:
                scope[op] = found.pop()
                changed = True
    return scope


# ---- the XSpace protobuf, as far as the event metadata goes

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints and fixed
    widths, memoryviews for length-delimited fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_metadata(xplane_path: str, stat: str = "tf_op") -> dict:
    """{HLO instruction name: (the `stat` string of its event metadata or
    None, the `%names` its HLO text refers to)} over the accelerator
    planes of an `*.xplane.pb`.

    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5 (maps: key 1, value 2); XEventMetadata.name = 2,
    .stats = 5; XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7 (a stat-metadata id whose name is the
    string)."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pn, pv in _fields(plane):
            if pn == 2:
                name = _text(pv)
            elif pn == 4:
                events.append(pv)
            elif pn == 5:
                entry = dict(_fields(pv))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = _text(meta.get(2, b""))
        if not (name.startswith("/device:") and "TPU" in name):
            continue
        for entry in events:
            ev_name = value = None
            for en, ev in _fields(dict(_fields(entry)).get(2, b"")):
                if en == 2:
                    ev_name = _text(ev)
                elif en == 5:
                    st = dict(_fields(ev))
                    if stat_names.get(st.get(1)) != stat:
                        continue
                    if 5 in st:
                        value = _text(st[5])
                    elif 7 in st:
                        value = stat_names.get(st[7])
            if ev_name:
                op = op_name(ev_name)
                out[op] = (value, tuple(
                    r for r in _REF_RE.findall(ev_name) if r != op))
    return out


def window_wall(xplane_path: str):
    """The `bench.window` span's (start, end) in wall-clock seconds and its
    length: the trace's times count from its `profile_start_time`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    start, spans = None, []
    for plane in pd.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
        elif plane.name.startswith("/host:CPU"):
            spans.extend((e.start_ns, e.end_ns) for line in plane.lines
                         for e in line.events if e.name == WINDOW_SPAN)
    if start is None or not spans:
        return None
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    t0 = start * 1e-9
    return t0 + lo * 1e-9, t0 + hi * 1e-9, (hi - lo) * 1e-9


def _this_run(ctx):
    """Temporary (see the module docstring).
    (path, window in wall seconds) of the run's own profile: the newest
    under `.bench_work/profile` whose window is the one `ctx["trace"]`
    was reduced from; None if there is none."""
    t = ctx.get("trace")
    if not t:
        return None
    paths = glob.glob(os.path.join(PROFILES, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    wall = window_wall(path)
    if wall is None or abs(wall[2] - t["window_s"]) > 1e-9:
        return None
    return path, wall[:2]


def scopes(ctx):
    """`ctx["scopes"]`: {HLO instruction name: scope} of the traced run,
    read on first use; None without a profile of this run."""
    if "scopes" not in ctx:
        run = _this_run(ctx)
        ctx["scopes"] = op_scopes(op_metadata(run[0])) if run else None
        ctx.setdefault("window_wall", run[1] if run else None)
    return ctx["scopes"]


def scope_seconds(ctx):
    """{scope: device self seconds in the window}, from `ctx["trace"]`'s
    per-op times; None if no op carries a scope (a program without them)."""
    t, by_op = ctx.get("trace"), scopes(ctx)
    if not t or not by_op:
        return None
    out = {}
    for op, secs in t["op_s"].items():
        s = by_op.get(op, OTHER)
        out[s] = out.get(s, 0.0) + secs
    return out if any(s in out for s in SCOPES) else None


def busy_share(ctx, scope: str):
    """Percent of the device's busy time spent in ops of `scope`."""
    t, secs = ctx.get("trace"), scope_seconds(ctx)
    if not secs or t["busy_s"] <= 0 or secs.get(scope, 0.0) <= 0:
        return None
    return 100.0 * secs[scope] / t["busy_s"]


def program(ctx):
    """`ctx["program"]`: the program's own counters (`repro.obs.spans`),
    read on first use; None on a program without them.

      lower_s, load_s    what `compile()` moved inside its `dfl.compile.lower`
                         and `dfl.compile.load` spans (the harness compiles
                         once);
      window_compiles    compile requests whose wall-clock span overlaps
                         the traced window (None without one)."""
    if "program" not in ctx:
        ctx["program"] = _read_program(ctx)
    return ctx["program"]


def _read_program(ctx):
    # temporary (see the module docstring): the process's cumulative table
    try:
        from repro.obs import spans
    except ImportError:
        return None
    table = spans.span_table()
    lower = table.get("dfl.compile.lower")
    load = table.get("dfl.compile.load")
    if lower is None or load is None:
        return None
    if "window_wall" not in ctx:
        scopes(ctx)
    wall = ctx.get("window_wall")
    return {
        "lower_s": lower["lower_s"], "load_s": load["load_s"],
        "window_compiles": None if wall is None else sum(
            1 for s, e in spans.compile_times()
            if s < wall[1] and e > wall[0]),
    }
