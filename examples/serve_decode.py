"""Serving example: batched autoregressive decoding with a KV cache.

Loads a reduced assigned architecture, prefills a batch of synthetic prompts
via the teacher-forced path, then decodes new tokens step by step (ring-
buffer cache, one jitted `decode_step` per token).

    PYTHONPATH=src python examples/serve_decode.py --arch mixtral-8x7b
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.models.lm import build_lm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen1.5-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    lm = build_lm(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)

    max_len = args.prompt_len + args.new_tokens
    cache = lm.init_cache(args.batch, max_len)
    if lm.prep_decode_cache is not None:  # enc-dec: run the encoder once
        enc = jnp.asarray(rng.standard_normal(
            (args.batch, max_len // cfg.enc_seq_divisor, cfg.d_model)) * 0.05,
            cfg.adtype)
        cache = lm.prep_decode_cache(params, cache, enc)

    step = jax.jit(lm.decode_step)
    # prefill: feed prompt tokens through the cache path
    for t in range(args.prompt_len):
        logits, cache = step(params, cache, prompts[:, t : t + 1])

    # decode: greedy sampling
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for _ in range(args.new_tokens - 1):
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out.append(tok)
    dt = time.time() - t0
    gen = np.concatenate([np.asarray(t) for t in out], axis=1)
    print(f"arch={args.arch} batch={args.batch} "
          f"decoded {args.new_tokens} tokens in {dt:.2f}s "
          f"({args.batch * args.new_tokens / dt:.1f} tok/s)")
    print("first sequence:", gen[0][:16], "...")
    assert np.isfinite(np.asarray(logits, np.float32)).all()


if __name__ == "__main__":
    main()
