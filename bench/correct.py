"""The comparison that decides `correct`.

Set-up drives the timed object through its first calls (a call is one
`run(rounds=R)` of the window) and keeps readings of each; the plain
reference (`bench/reference.py`) follows the same calls from the same
weights and data.  The numbers compared, each with a limit of its own from
`bench/limits/<cell>.json`:

  loss0   first round's mean eval loss over nodes, relative gap
  lossK   mean eval loss over nodes after call K, relative gap
  acc     largest gap of mean eval accuracy after any call, absolute
  mom1    optimizer momentum after call 1: worst leaf's gap of norms
  dparam  parameter change over all calls: worst leaf's gap of norms
  bytes   bytes on the wire over all calls, relative gap (exact: limit 0)

A worst-leaf gap is |norm_program - norm_reference| over the larger of the
reference's norm of that leaf and of the median leaf.  Leaves whose
reference momentum is under a thousandth of the median leaf's (a gradient
that is nought to rounding) are left out of both worst-leaf numbers.
"""
from __future__ import annotations

import json
import os
import statistics

HERE = os.path.dirname(os.path.abspath(__file__))
TINY_GRAD = 1e-3


def _rel(p: float, r: float) -> float:
    return abs(p - r) / abs(r) if r else abs(p - r)


def _worst_leaf(prog: dict, ref: dict, keep) -> float:
    med = statistics.median(ref.values())
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def gaps(prog: dict, ref: dict) -> dict:
    """{name: gap} of the program's readings against the reference's."""
    med = statistics.median(ref["mom1"].values())
    keep = [k for k, v in ref["mom1"].items() if v >= TINY_GRAD * med]
    out = {"loss0": _rel(prog["loss0"], ref["loss0"])}
    for i, (p, r) in enumerate(zip(prog["loss"], ref["loss"])):
        out[f"loss{i + 1}"] = _rel(p, r)
    out["acc"] = max(abs(p - r) for p, r in zip(prog["acc"], ref["acc"]))
    out["mom1"] = _worst_leaf(prog["mom1"], ref["mom1"], keep)
    out["dparam"] = _worst_leaf(prog["dparam"], ref["dparam"], keep)
    if "bytes" in prog:
        out["bytes"] = _rel(prog["bytes"], ref["bytes"])
    return out


def limits(cell: str) -> dict:
    with open(os.path.join(HERE, "limits", f"{cell}.json")) as f:
        return json.load(f)["limits"]


def judge(found: dict, lim: dict):
    """(correct, checks): every number at or under its limit."""
    checks = {k: {"value": v, "limit": lim[k]} for k, v in found.items()}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
