"""Model FLOPs of the traced window over its seconds, over chips x the
bf16 peak: training (forward + backward, 3 x 2 x MACs per sample) and the
evals' forward passes; recomputation is not counted."""


def read(ctx):
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    flops = ctx["flops_per_call"] * ctx["calls_traced"]
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / t["window_s"] / peak
