"""Minibatches read as windows of the stride-ordered shards give exactly
the samples of the per-row stride gather they replace: every step, shard
sizes under, at and over the batch, image and token samples."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.allocation import pad_node_datasets, stride_ordered_shards
from repro.data.pipeline import Batcher

B = 32
M = 4245  # the paper world's largest shard
STEPS = 400
COUNTS = (1, 5, 32, 33, 244, M)
SAMPLES = {"images": (28, 28), "tokens": (16,)}


def _shard(count, kind):
    """`count` samples whose every value names its sample and position:
    float images with integer labels, or int32 tokens with [S] labels."""
    shape = SAMPLES[kind]
    size = int(np.prod(shape))
    ids = (np.arange(count)[:, None] * size + np.arange(size)).reshape(
        (count,) + shape)
    if kind == "images":
        return ids.astype(np.float32), np.arange(count, dtype=np.int32)
    return ids.astype(np.int32), -ids.astype(np.int32)


def _parent_take(x_pad, y_pad, count, step):
    """The per-row stride gather from the padded shard, as the window's
    predecessor took it."""
    idx = (step * B + jnp.arange(B, dtype=jnp.int32)) * Batcher.stride
    idx = idx % jnp.maximum(count, 1)
    return jnp.take(x_pad, idx, axis=0), jnp.take(y_pad, idx, axis=0)


def _per_step(take):
    """`take` over nodes as training calls it, then over steps."""
    return jax.jit(jax.vmap(jax.vmap(take, in_axes=(0, 0, 0, None)),
                            in_axes=(None, None, None, 0)))


@pytest.mark.parametrize("kind", sorted(SAMPLES))
@pytest.mark.parametrize("count", COUNTS)
def test_window_take_is_the_stride_gather(count, kind):
    xs, ys = zip(_shard(count, kind), _shard(M, kind))
    x_tab, y_tab, counts = stride_ordered_shards(list(xs), list(ys), B,
                                                 Batcher.stride)
    x_pad, y_pad, _ = pad_node_datasets(list(xs), list(ys))
    assert x_tab.shape == (2, M + B - 1, np.prod(SAMPLES[kind]))
    assert list(counts) == [count, M]
    steps = jnp.arange(STEPS, dtype=jnp.int32)
    c = jnp.asarray(counts, jnp.int32)
    batcher = Batcher(batch_size=B, sample_shape=SAMPLES[kind])
    got = _per_step(batcher.take)(x_tab, y_tab, c, steps)
    want = _per_step(_parent_take)(x_pad, y_pad, c, steps)
    for g, w in zip(got, want):
        assert g.shape == (STEPS, 2, B) + w.shape[3:]
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("kind", sorted(SAMPLES))
def test_neighbour_batches_are_the_stride_gather(kind):
    """The CFA-GE walk's read of its senders' batches (dense layout: slot d
    of round r is step r*max_deg + d), against the per-row gather from the
    padded shards it replaces."""
    xs, ys = zip(*(_shard(c, kind) for c in COUNTS))
    x_tab, y_tab, counts = stride_ordered_shards(list(xs), list(ys), B,
                                                 Batcher.stride)
    x_tab, y_tab = jnp.asarray(x_tab), jnp.asarray(y_tab)
    x_pad, y_pad, _ = map(jnp.asarray, pad_node_datasets(list(xs), list(ys)))
    c = jnp.asarray(counts, jnp.int32)
    j = jnp.asarray([5, 0, 3, 3, 1, 2, 4, 0], jnp.int32)  # senders of a slot
    max_deg = 16
    steps = jnp.asarray([r * max_deg + d for r in (0, 1, 7, 99)
                         for d in range(max_deg)], jnp.int32)

    def parent(step):
        bidx = (step * B + jnp.arange(B, dtype=jnp.int32)[None, :]) \
            * Batcher.stride
        bidx = bidx % jnp.maximum(c[j][:, None], 1)
        return x_pad[j[:, None], bidx], y_pad[j[:, None], bidx]

    read = Batcher(batch_size=B, sample_shape=SAMPLES[kind]).take_nodes
    got = jax.jit(jax.vmap(lambda t: read(x_tab, y_tab, c, j, t)))(steps)
    want = jax.jit(jax.vmap(parent))(steps)
    for g, w in zip(got, want):
        assert g.shape == (len(steps), len(j), B) + w.shape[3:]
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
