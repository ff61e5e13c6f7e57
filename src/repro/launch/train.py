"""End-to-end training driver.

Two modes:
  * ``--mode dfl`` (default): P-node decentralized federated training of an
    assigned architecture (reduced preset for CPU) with DecDiff gossip
    between nodes each round — the paper's Algorithm 1 at LM scale.
  * ``--mode single``: plain data-parallel training (the "centralized"
    reference at the systems level).

This is the driver of the legacy LM round (`repro.dist.dfl_step`); the
engine's rounds go through `repro.engine.Experiment`.  It runs the reduced
configs on the host CPU.  Synthetic token streams stand in for the data
pipeline (repro.data.tokens).

Example (CPU, ~100M-params-class run):
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --preset reduced --steps 200 --nodes 2 --log-every 10
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import ARCH_IDS, get_config
from repro.data.tokens import synthetic_token_batch
from repro.dist.dfl_step import build_dfl_round, build_train_step
from repro.models.lm import build_lm
from repro.optim.sgd import sgd_momentum
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.pytree import tree_size


def make_batches(lm, nodes, batch, seq, steps, seed=0):
    """Pre-generate a deterministic synthetic token stream per node."""
    for step in range(steps):
        bs = []
        for node in range(max(nodes, 1)):
            b = synthetic_token_batch(batch, seq, lm.cfg.vocab,
                                      seed=seed + step * 131 + node)
            bs.append(b)
        if nodes == 0:
            yield {k: jnp.asarray(v) for k, v in bs[0].items()}
        else:
            yield {k: jnp.asarray(np.stack([b[k] for b in bs]))
                   for k in bs[0]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen1.5-0.5b")
    ap.add_argument("--preset", choices=["reduced", "full"], default="reduced")
    ap.add_argument("--mode", choices=["dfl", "single"], default="dfl")
    ap.add_argument("--nodes", type=int, default=2, help="DFL nodes (pods)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--beta", type=float, default=0.98, help="VT confidence")
    ap.add_argument("--loss", choices=["vt", "ce"], default="vt")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.preset == "reduced":
        cfg = cfg.reduced(n_layers=4, d_model=256, vocab=2048)
    lm = build_lm(cfg)
    opt = sgd_momentum(lr=args.lr, momentum=0.9)

    if args.mode == "single":
        params = lm.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        step_fn = jax.jit(build_train_step(lm, opt, loss_kind=args.loss,
                                           beta=args.beta))
        stream = make_batches(lm, 0, args.batch, args.seq, args.steps)
    else:
        keys = jax.random.split(jax.random.PRNGKey(0), args.nodes)
        params = jax.vmap(lm.init)(keys)  # heterogeneous init per node
        opt_state = jax.vmap(opt.init)(params)
        adj = np.zeros((args.nodes, args.nodes), np.float32)
        for i in range(args.nodes):
            adj[i, (i + 1) % args.nodes] = adj[i, (i - 1) % args.nodes] = 1.0
        adj /= np.maximum(adj.sum(1, keepdims=True), 1)
        step_fn = jax.jit(build_dfl_round(lm, opt, jnp.asarray(adj),
                                          loss_kind=args.loss, beta=args.beta))
        stream = make_batches(lm, args.nodes, args.batch, args.seq, args.steps)

    n_params = tree_size(params)
    print(f"arch={args.arch} preset={args.preset} mode={args.mode} "
          f"params={n_params/1e6:.1f}M loss={args.loss}")

    t0 = time.time()
    losses = []
    for step, batch in enumerate(stream):
        params, opt_state, loss = step_fn(params, opt_state, jnp.int32(step), batch)
        losses.append(float(loss))
        if step % args.log_every == 0 or step == args.steps - 1:
            rate = (step + 1) / (time.time() - t0)
            print(f"step {step:5d}  loss {float(loss):.4f}  {rate:.2f} it/s",
                  flush=True)
    if args.ckpt_dir:
        path = save_checkpoint(args.ckpt_dir, args.steps,
                               {"params": params, "opt": opt_state},
                               metadata={"arch": args.arch, "mode": args.mode})
        print("checkpoint:", path)
    assert np.isfinite(losses[-1]), "training diverged"
    print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f})")


if __name__ == "__main__":
    main()
