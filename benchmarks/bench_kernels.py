"""Kernel micro-benchmarks.

On this CPU container the Pallas kernels execute in interpret mode (a
correctness harness, not a perf mode), so wall-times compare the pure-jnp
reference implementations (XLA-compiled on CPU) and report the kernels'
expected TPU roofline instead: all three are HBM-streaming ops, so
t_expected = bytes_moved / 819 GB/s per chip."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save_results
from repro.kernels.ref import decdiff_update_ref, neighbor_avg_ref, vt_kl_loss_ref
from repro.utils.compile_cache import enable_compile_cache

HBM_BW = 819e9


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6  # us


def run(verbose=True):
    rows = []
    rng = np.random.default_rng(0)

    # decdiff_update: streams 3 model-sized vectors (read w, wbar; write w')
    for n in (1 << 20, 1 << 24):
        w = jnp.asarray(rng.standard_normal(n), jnp.float32)
        wb = jnp.asarray(rng.standard_normal(n), jnp.float32)
        f = jax.jit(decdiff_update_ref)
        us = _time(f, w, wb)
        bytes_moved = 3 * 4 * n
        rows.append({"name": f"decdiff_update/n={n}", "cpu_ref_us": us,
                     "tpu_roofline_us": bytes_moved / HBM_BW * 1e6})

    # vt_kl_loss: streams logits once (stats) — B*V fp32 read
    for (b, v) in ((256, 32000), (64, 151936)):
        z = jnp.asarray(rng.standard_normal((b, v)), jnp.float32)
        y = jnp.asarray(rng.integers(0, v, b), jnp.int32)
        f = jax.jit(lambda zz, yy: vt_kl_loss_ref(zz, yy, 0.95))
        us = _time(f, z, y)
        rows.append({"name": f"vt_kl_loss/b={b},v={v}", "cpu_ref_us": us,
                     "tpu_roofline_us": (4 * b * v) / HBM_BW * 1e6})

    # neighbor_avg: streams N stacked models
    for (n, d) in ((8, 1 << 22), (16, 1 << 20)):
        st = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        wts = jnp.asarray(rng.random(n) + 0.5, jnp.float32)
        f = jax.jit(neighbor_avg_ref)
        us = _time(f, st, wts)
        rows.append({"name": f"neighbor_avg/n={n},d={d}", "cpu_ref_us": us,
                     "tpu_roofline_us": (4 * n * d) / HBM_BW * 1e6})

    save_results("kernel_bench", rows)
    if verbose:
        for r in rows:
            print(f"{r['name']:32s} cpu_ref {r['cpu_ref_us']:10.1f} us   "
                  f"tpu_roofline {r['tpu_roofline_us']:8.1f} us")
    return rows


def main():
    run()


if __name__ == "__main__":
    enable_compile_cache()
    main()
