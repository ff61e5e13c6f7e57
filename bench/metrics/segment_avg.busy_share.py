"""The `segment_avg` kernel's device time over the device's busy time."""

KERNEL = "segment_avg"


def read(ctx):
    from bench.trace import kernel_seconds

    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0:
        return None
    spent = kernel_seconds(t, KERNEL)
    return 100.0 * spent / t["busy_s"] if spent > 0 else None
