"""The neighbour reduce's least time over the `segment_avg` kernel's device
time.  The least time counts what any implementation of the reduce must
do, from the cell's shapes: 2*E*D flops, and E*D neighbour values read at
the width they crossed the wire plus N*D float32 sums written, once per
reduce call (the kernel's occurrences in the round's compiled HLO)."""

KERNEL = "segment_avg"


def least_seconds(ctx):
    e, d, n = ctx["directed_edges"], ctx["params_per_node"], ctx["nodes"]
    flops = 2.0 * e * d
    nbytes = e * d * ctx["traffic"]["wire_bytes_per_value"] + n * d * 4.0
    p = ctx["peaks"]
    return max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])


def read(ctx):
    from bench.trace import kernel_seconds

    t = ctx.get("trace")
    calls = ctx["rounds_traced"] * sum(
        n for name, n in ctx["kernels"].items() if KERNEL in name)
    if not t or not calls:
        return None
    spent = kernel_seconds(t, KERNEL)
    if spent <= 0:
        return None
    return 100.0 * calls * least_seconds(ctx) / spent
