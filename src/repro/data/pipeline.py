"""Minimal-but-real data pipeline: deterministic shuffled minibatching.

Two entry points:
  * :func:`minibatches` — host-side generator over numpy arrays (used by the
    centralized / FedAvg baselines and examples).
  * :class:`Batcher` — device-side batcher usable inside jit/vmap (used by
    the multi-node simulator, where each of N nodes draws from its own
    shard with its own rng-free deterministic stride schedule): each
    minibatch is one contiguous window of the node's stride-ordered shard
    (:func:`repro.data.allocation.stride_ordered_shards`).
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax


def minibatches(x: np.ndarray, y: np.ndarray, batch_size: int, *, rng: np.random.Generator,
                drop_remainder: bool = True) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    n = len(x)
    order = rng.permutation(n)
    end = (n // batch_size) * batch_size if drop_remainder else n
    for s in range(0, max(end, 0), batch_size):
        ix = order[s : s + batch_size]
        yield x[ix], y[ix]


@dataclasses.dataclass(frozen=True)
class Batcher:
    """Deterministic stride-order batching inside jit.

    Step t of a node with `count` = c real samples trains on the samples
    `((t*bs + j) * stride) mod c`, j < bs.  A prime stride decorrelates
    consecutive batches without a shuffle (inside vmap per-node
    permutations would be ragged).  `take` reads that batch as the window
    of bs rows from `(t*bs) mod c` of the node's shard laid out by
    :func:`repro.data.allocation.stride_ordered_shards` with the same
    `batch_size` and `stride`: one contiguous slice, not bs row gathers.
    The shard holds each x sample as one flat row; `take` gives the
    window's x back as `[bs, *sample_shape]`.

    The window needs no product with the stride, so its int32 arithmetic
    wraps only past 2**31 / bs steps.  The earlier per-row gather formed
    `(t*bs + j) * stride` in int32, which wrapped from step 8,474 on (at
    bs 32); below that the two read the same samples.
    """

    batch_size: int
    stride: int = 7919  # prime
    sample_shape: Tuple[int, ...] = ()  # of one x sample

    def take(self, x: jnp.ndarray, y: jnp.ndarray, count: jnp.ndarray, step: jnp.ndarray):
        start = (step.astype(jnp.int32) * self.batch_size
                 ) % jnp.maximum(count.astype(jnp.int32), 1)
        xb = lax.dynamic_slice_in_dim(x, start, self.batch_size, axis=0)
        return (xb.reshape((self.batch_size,) + self.sample_shape),
                lax.dynamic_slice_in_dim(y, start, self.batch_size, axis=0))

    def take_nodes(self, x: jnp.ndarray, y: jnp.ndarray, counts: jnp.ndarray,
                   nodes: jnp.ndarray, step: jnp.ndarray):
        """The batch of `step` of each node in `nodes` ([r] ids), read from
        every node's stride-ordered shards `x`, `y` ([N, rows, ...]) with
        their `counts` ([N]): ([r, batch_size, ...], [r, batch_size, ...]),
        the same samples `take` gives each node alone."""
        rows = (step.astype(jnp.int32) * self.batch_size
                + jnp.arange(self.batch_size, dtype=jnp.int32)[None, :]
                ) % jnp.maximum(counts[nodes].astype(jnp.int32)[:, None], 1)
        xb = x[nodes[:, None], rows]
        return (xb.reshape(rows.shape + self.sample_shape),
                y[nodes[:, None], rows])
