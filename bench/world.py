"""The benchmark's own seeded generators for the paper's worlds.

A frozen copy of the generators the program ships (synthetic 28x28 class-
prototype images, the truncated-Zipf non-IID split, the Erdos-Renyi graph
and its padded neighbour layout), so that a change to the program cannot
change the benchmark's inputs.  `bench/tests/test_world.py` pins that the
copy gave the same arrays as the program's `World.synthetic` when it was
taken.

Everything here is numpy on the host and deterministic in the seed; seeds
may exceed 32 bits.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import List

import numpy as np

# Image sets: 28x28 grayscale, 10 classes (the shapes of MNIST and
# Fashion-MNIST; the configuration gives the sample counts).  Noise scales
# as the program tuned them.
DATASETS = {
    "synth-mnist": dict(num_classes=10, prototypes_per_class=4,
                        pixel_noise=0.15, deform_noise=0.30, mix_alpha=0.8),
    "synth-fashion": dict(num_classes=10, prototypes_per_class=4,
                          pixel_noise=0.22, deform_noise=0.45,
                          mix_alpha=0.8),
}
HW = (28, 28)


def _smooth_field(rng, hw, low=7):
    h, w = hw
    coarse = rng.standard_normal((low, low)).astype(np.float32)
    yi = np.linspace(0, low - 1, h)
    xi = np.linspace(0, low - 1, w)
    y0 = np.floor(yi).astype(int)
    x0 = np.floor(xi).astype(int)
    y1 = np.minimum(y0 + 1, low - 1)
    x1 = np.minimum(x0 + 1, low - 1)
    fy = (yi - y0)[:, None]
    fx = (xi - x0)[None, :]
    f = (coarse[np.ix_(y0, x0)] * (1 - fy) * (1 - fx)
         + coarse[np.ix_(y1, x0)] * fy * (1 - fx)
         + coarse[np.ix_(y0, x1)] * (1 - fy) * fx
         + coarse[np.ix_(y1, x1)] * fy * fx)
    return f.astype(np.float32)


def _normalize01(a):
    lo, hi = a.min(), a.max()
    return (a - lo) / max(hi - lo, 1e-6)


def _split(rng, protos, n, spec):
    c, k, h, w = protos.shape
    labels = rng.integers(0, c, size=n).astype(np.int32)
    mix = rng.dirichlet(np.full(k, spec["mix_alpha"]), size=n).astype(
        np.float32)
    base = np.einsum("nk,nkhw->nhw", mix, protos[labels])
    imgs = np.empty((n, h, w), np.float32)
    chunk = 4096
    reps = (h + 6) // 7
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        deform = rng.standard_normal((e - s, 7, 7)).astype(np.float32)
        deform_up = np.kron(deform, np.ones((1, reps, reps),
                                            np.float32))[:, :h, :w]
        noise = rng.standard_normal((e - s, h, w)).astype(np.float32)
        imgs[s:e] = (base[s:e] + spec["deform_noise"] * deform_up
                     + spec["pixel_noise"] * noise)
    imgs = np.clip((imgs - imgs.min()) / max(imgs.max() - imgs.min(), 1e-6),
                   0, 1)
    return imgs, labels


def make_images(name: str, seed: int, train_size: int, test_size: int):
    """(x_train, y_train, x_test, y_test), standardized by train stats."""
    spec = DATASETS[name]
    rng = np.random.default_rng([zlib.crc32(name.encode()), seed])
    protos = np.stack([
        np.stack([_normalize01(_smooth_field(rng, HW))
                  for _ in range(spec["prototypes_per_class"])])
        for _ in range(spec["num_classes"])])
    x_tr, y_tr = _split(rng, protos, train_size, spec)
    x_te, y_te = _split(rng, protos, test_size, spec)
    mean, std = x_tr.mean(), x_tr.std() + 1e-6
    return (x_tr - mean) / std, y_tr, (x_te - mean) / std, y_te


def zipf_split(labels, num_nodes: int, alpha: float, min_per_class: int,
               seed: int) -> List[np.ndarray]:
    """Per-class truncated-Zipf shares over a per-class random node
    ranking, with a per-node per-class floor; sorted index arrays."""
    rng = np.random.default_rng(seed)
    labels = np.asarray(labels)
    node_indices: List[List[int]] = [[] for _ in range(num_nodes)]
    rng.permutation(num_nodes)  # the program draws a global ranking here
    shares = 1.0 / np.power(np.arange(1, num_nodes + 1, dtype=np.float64),
                            alpha)
    shares = shares / shares.sum()
    for c in np.unique(labels):
        idx = np.nonzero(labels == c)[0]
        rng.shuffle(idx)
        n_c = len(idx)
        ranks = rng.permutation(num_nodes)
        floor = min(min_per_class, max(n_c // num_nodes, 1))
        remaining = n_c - floor * num_nodes
        if remaining < 0:
            floor, remaining = 0, n_c
        counts = np.full(num_nodes, floor, np.int64)
        counts[ranks] += np.floor(shares * remaining).astype(np.int64)
        order = ranks[np.argsort(-shares)]
        for k in range(int(n_c - counts.sum())):
            counts[order[k % num_nodes]] += 1
        off = 0
        for node in range(num_nodes):
            node_indices[node].extend(idx[off:off + int(counts[node])])
            off += int(counts[node])
    return [np.asarray(sorted(ix), np.int64) for ix in node_indices]


def erdos_renyi(n: int, p: float, seed: int) -> np.ndarray:
    """Symmetric {0,1} int8 adjacency of the first connected G(n, p) draw
    (networkx's generator, seeds seed, seed + 10007, ...)."""
    import networkx as nx

    for attempt in range(64):
        g = nx.erdos_renyi_graph(n, p, seed=seed + attempt * 10007)
        adj = nx.to_numpy_array(g, dtype=np.int8)
        np.fill_diagonal(adj, 0)
        adj = np.maximum(adj, adj.T)
        if nx.is_connected(g):
            return adj
    raise RuntimeError(f"no connected ER({n},{p}) graph in 64 draws")


def padded_neighbors(adj: np.ndarray):
    """Row i's neighbours ascending, padded with -1 to the max degree."""
    n = adj.shape[0]
    degs = adj.sum(axis=1).astype(np.int64)
    max_deg = max(int(degs.max()), 1)
    nbr = -np.ones((n, max_deg), np.int32)
    for i in range(n):
        (cols,) = np.nonzero(adj[i])
        nbr[i, :cols.size] = cols
    return nbr


@dataclasses.dataclass
class PaperWorld:
    """One seeded world: per-node shards, the test set and the graph."""

    xs: List[np.ndarray]
    ys: List[np.ndarray]
    x_test: np.ndarray
    y_test: np.ndarray
    adjacency: np.ndarray   # [N, N] int8, symmetric, zero diagonal
    nbr_idx: np.ndarray     # [N, max_deg] int32, -1 padded

    @property
    def num_nodes(self) -> int:
        return len(self.xs)

    @property
    def num_directed_edges(self) -> int:
        return int(self.adjacency.sum())


def build_world(world_cfg: dict) -> PaperWorld:
    """The world a configuration file's `world` block describes, drawn
    from its `seed`."""
    seed = world_cfg["seed"]
    x_tr, y_tr, x_te, y_te = make_images(
        world_cfg["dataset"], seed, world_cfg["train_size"],
        world_cfg["test_size"])
    n = world_cfg["nodes"]
    alloc = zipf_split(y_tr, n, world_cfg["zipf_alpha"],
                       world_cfg["min_per_class"], seed)
    adj = erdos_renyi(n, world_cfg["er_p"], seed)
    return PaperWorld(xs=[x_tr[ix] for ix in alloc],
                      ys=[y_tr[ix] for ix in alloc], x_test=x_te,
                      y_test=y_te, adjacency=adj,
                      nbr_idx=padded_neighbors(adj))
