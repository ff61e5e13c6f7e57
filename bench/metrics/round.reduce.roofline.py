"""The neighbour reduce's least time over the device time of the whole
`dfl.reduce` scope: gather, pad and kernel together.  The least time and
the count of reduces are `segment_avg.roofline`'s (its `least_seconds`,
once per kernel occurrence per traced round), so a change that moves work
between the kernel and the copies around it moves this share only by what
the path as a whole saves."""
import importlib.util
import os

SCOPE = "dfl.reduce"
KERNEL = "segment_avg"


def _kernel_roofline():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "segment_avg.roofline.py")
    spec = importlib.util.spec_from_file_location("segment_avg_roofline",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(ctx):
    from bench.scopes import scope_seconds

    secs = scope_seconds(ctx)
    calls = ctx["rounds_traced"] * sum(
        n for name, n in ctx["kernels"].items() if KERNEL in name)
    if not secs or not calls or secs.get(SCOPE, 0.0) <= 0:
        return None
    least = _kernel_roofline().least_seconds(ctx)
    return 100.0 * calls * least / secs[SCOPE]
