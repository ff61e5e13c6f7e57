"""Neighborhood views: ONE flat gossip reduce for both node-axis layouts.

The engine's gossip strategies aggregate through a `Neighborhood` — an
object exposing the five primitives a coordination-free update needs:

  * ``local()``        — the block's own models as one [R, D] fp32 matrix;
  * ``reduce()``       — (Σ_k w·x_k [R, D], Σ_k w [R]) over delivered
    neighbour models;
  * ``reduce_delta()`` — the same contraction over (x_k - local);
  * ``n_active()``     — the count of delivered neighbours per receiver;
  * ``unflatten(out)`` — back to the params pytree.

Two implementations share those semantics bit-for-bit:

  * :class:`DenseNeighborhood` — the `[R, max_deg]` padded layout over a
    full `[N, D]` model table (the small-N oracle);
  * :class:`SparseNeighborhood` — degree-bucketed ragged edge blocks from a
    :class:`SparsePlan` (CSR edge list → per-pod per-width slot tables),
    O(N + E) state instead of O(N·max_deg).

Both evaluate every per-receiver contraction through
`repro.kernels.ops.segment_neighbor_avg_rows`: its input is the `[M, D]`
table itself (the decoded models, or the sparse per-edge bank) with
`[B, K]` row ids, and its kernel gathers each receiver's rows from HBM and
contracts each receiver row independently — so the reduce is bitwise
invariant to row blocking (vmap's R=N vs a pod's R=N/P) and to K-width
zero padding (the dense max_deg slots vs a sparse bucket's power-of-two
width).  Only the per-edge transport's per-link reconstructions and the
delta forms (x_k - local) exist as a `[R, K, D]` panel; they go through
`segment_neighbor_avg`, the same kernel over the flattened panel.  Totals
ride the contraction (a separate `jnp.sum(w)` would not be
width-invariant), and normalization happens AFTER the reduce, on per-row
scalars, in the strategy's `flat_aggregate`.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import (
    segment_neighbor_avg,
    segment_neighbor_avg_rows,
)
from repro.obs.spans import REDUCE


class WidthBucket(NamedTuple):
    """One degree bucket's slot tables, stacked over the pod axis.

    All arrays lead with [P, B] (B = the bucket's receiver count, padded to
    the max over pods with inert dummy rows: rows_local = per_pod → the
    scatter trash row, wgt = 0)."""

    rows_local: jnp.ndarray  # [P, B] int32, receiver row within the pod
    src: jnp.ndarray         # [P, B, K] int32 sender node ids (pad 0)
    wgt: jnp.ndarray         # [P, B, K] f32 ω_e·|D_src| (pad 0)
    epos: jnp.ndarray        # [P, B, K] int32 directed-edge position (pad 0)


class SparsePlan(NamedTuple):
    """The static ragged layout: everything the round body needs to gossip
    over a :class:`~repro.graphs.SparseTopology` without dense [N, N] or
    [N, max_deg] state."""

    widths: Tuple[int, ...]          # static ascending bucket widths
    buckets: Dict[int, WidthBucket]  # width -> stacked slot tables
    degrees: jnp.ndarray             # [N] f32 in-degree (byte accounting)
    num_directed: int
    per_pod: int
    n_pods: int


def _bucket_width(deg: int) -> int:
    """Per-receiver slot width: next power of two, floor 8 — total padded
    slots are ≤ 2E + 8N, vs N·max_deg for the dense layout (O(N^2) on
    hubs)."""
    return max(8, 1 << int(np.ceil(np.log2(max(deg, 1)))))


def build_sparse_plan(st, counts: np.ndarray, n_pods: int) -> SparsePlan:
    """Lay a SparseTopology out as per-pod, per-width slot tables.

    Nodes map to pods in contiguous blocks (node i → pod i // per_pod), the
    same row blocks the shard_map backend slices; `counts` are the per-node
    |D_i| data sizes folded into the gossip weights exactly as the dense
    layout folds them (ω_e · |D_src| in float32, in that order)."""
    n = st.num_nodes
    if n % n_pods:
        raise ValueError(f"{n} nodes do not tile {n_pods} pods")
    per_pod = n // n_pods
    offsets = st.row_offsets
    degs = np.diff(offsets).astype(np.int64)
    counts = np.asarray(counts)
    wgt_edge = st.edge_weight * counts[st.edge_src].astype(np.float32)
    widths = sorted({_bucket_width(int(d)) for d in degs})
    node_width = np.array([_bucket_width(int(d)) for d in degs])

    buckets = {}
    for wd in widths:
        per_pod_rows = []
        for p in range(n_pods):
            block = np.arange(p * per_pod, (p + 1) * per_pod)
            per_pod_rows.append(block[node_width[block] == wd])
        b = max(r.shape[0] for r in per_pod_rows)
        rows_local = np.full((n_pods, b), per_pod, np.int32)
        src = np.zeros((n_pods, b, wd), np.int32)
        wgt = np.zeros((n_pods, b, wd), np.float32)
        epos = np.zeros((n_pods, b, wd), np.int32)
        for p, nodes in enumerate(per_pod_rows):
            for k, i in enumerate(nodes):
                lo, hi = int(offsets[i]), int(offsets[i + 1])
                d = hi - lo
                rows_local[p, k] = i - p * per_pod
                src[p, k, :d] = st.edge_src[lo:hi]
                wgt[p, k, :d] = wgt_edge[lo:hi]
                epos[p, k, :d] = np.arange(lo, hi)
        buckets[wd] = WidthBucket(
            rows_local=jnp.asarray(rows_local), src=jnp.asarray(src),
            wgt=jnp.asarray(wgt), epos=jnp.asarray(epos))

    return SparsePlan(
        widths=tuple(widths), buckets=buckets,
        degrees=jnp.asarray(degs.astype(np.float32)),
        num_directed=st.num_directed, per_pod=per_pod, n_pods=n_pods)


class DenseNeighborhood:
    """The padded-layout view: table [N, D], nbr_idx/w [R, max_deg].

    When the transport has ALREADY materialized the per-slot neighbour
    models (the per-edge transport's reverse-slot gather yields per-link
    reconstructions that need not agree across receivers, so no single
    [N, D] table exists), pass them as ``panel`` [R, max_deg, D] instead of
    ``table``/``nbr_idx`` — the reduce contracts the panel directly through
    the same kernel, so the bits match the table form whenever the values
    do."""

    def __init__(self, table, nbr_idx, w, local_mat, unflatten_fn,
                 panel=None):
        self.table = table
        self.nbr_idx = nbr_idx
        self.w = w
        self.local_mat = local_mat
        self._unflatten = unflatten_fn
        self.panel = panel

    def _vals(self):
        return (self.panel if self.panel is not None
                else self.table[self.nbr_idx])

    def local(self):
        return self.local_mat

    @jax.named_scope(REDUCE)
    def reduce(self):
        if self.panel is not None:
            return segment_neighbor_avg(self.panel, self.w)
        return segment_neighbor_avg_rows(self.table, self.nbr_idx, self.w)

    @jax.named_scope(REDUCE)
    def reduce_delta(self):
        vals = self._vals() - self.local_mat[:, None, :]
        return segment_neighbor_avg(vals, self.w)

    def n_active(self):
        return jnp.sum((self.w > 0).astype(jnp.float32), axis=1)

    def unflatten(self, out):
        return self._unflatten(out)


class SparseNeighborhood:
    """The ragged view: per-width buckets gathered from a full [N, D] table,
    scattered back to pod rows through a trash slot (row R of an [R+1]
    accumulator; dummy bucket rows land there and are sliced away).

    `gate_vec` [N] {0,1} are the senders' broadcast gates (trigger fired /
    ever-sent; None skips the factor entirely — e.g. the per-edge transport
    folds its gates into `edge_mask` instead); `link_u` [E] are this
    round's replicated per-directed-edge uniforms (None when participation
    == 1).  All gate factors are exact {0,1} floats, so the composed
    weights equal the dense layout's ω_e·|D_src|·gate·link products
    bit-for-bit.

    Two optional [E] edge-indexed inputs extend the view to the full
    scenario matrix without changing the reduce:

      * ``edge_table`` [E, D] — per-DIRECTED-EDGE values (the sparse
        per-edge transport's reconstruction bank): bucket slots then gather
        `edge_table[epos]` instead of `table[src]`, the flat-edge analogue
        of the dense panel form (receiver slots ARE CSR edge positions, so
        no reverse gather is needed);
      * ``edge_mask`` [E] {0,1} — a per-directed-edge weight factor (a
        dynamics live mask, or the per-edge transport's aggregation mask),
        applied through `epos` exactly where the dense layout multiplies
        its `[N, max_deg]` mask panel.

    Padding slots point at edge 0 (finite garbage) with wgt = 0, which the
    `segment_neighbor_avg_rows` kernel contract makes bit-neutral."""

    def __init__(self, plan: SparsePlan, pod, table, local_mat, unflatten_fn,
                 gate_vec, link_u, participation: float, *,
                 edge_table=None, edge_mask=None):
        self.plan = plan
        self.pod = pod
        self.table = table
        self.local_mat = local_mat
        self._unflatten = unflatten_fn
        self.gate_vec = gate_vec
        self.link_u = link_u
        self.participation = participation
        self.edge_table = edge_table
        self.edge_mask = edge_mask

    def _take(self, a):
        """Select this pod's slab of a [P, ...] plan array."""
        return jax.lax.dynamic_index_in_dim(a, self.pod, axis=0,
                                            keepdims=False)

    def _weights(self, src, wgt, epos):
        w = wgt if self.gate_vec is None else wgt * self.gate_vec[src]
        if self.participation < 1.0:
            w = w * (self.link_u[epos] < self.participation).astype(
                jnp.float32)
        if self.edge_mask is not None:
            w = w * self.edge_mask[epos]
        return w

    def local(self):
        return self.local_mat

    @jax.named_scope(REDUCE)
    def _reduce(self, delta: bool):
        r, d = self.local_mat.shape
        sums = jnp.zeros((r + 1, d), jnp.float32)
        tot = jnp.zeros((r + 1,), jnp.float32)
        local_pad = jnp.concatenate(
            [self.local_mat, jnp.zeros((1, d), jnp.float32)])
        for wd in self.plan.widths:
            bk = self.plan.buckets[wd]
            rows_local = self._take(bk.rows_local)
            src = self._take(bk.src)
            epos = self._take(bk.epos)
            table, idx = ((self.edge_table, epos)
                          if self.edge_table is not None
                          else (self.table, src))
            w = self._weights(src, self._take(bk.wgt), epos)
            if delta:
                vals = table[idx] - local_pad[rows_local][:, None, :]
                s, t = segment_neighbor_avg(vals, w)
            else:
                s, t = segment_neighbor_avg_rows(table, idx, w)
            sums = sums.at[rows_local].set(s)
            tot = tot.at[rows_local].set(t)
        return sums[:r], tot[:r]

    def reduce(self):
        return self._reduce(delta=False)

    def reduce_delta(self):
        return self._reduce(delta=True)

    def n_active(self):
        r = self.local_mat.shape[0]
        na = jnp.zeros((r + 1,), jnp.float32)
        for wd in self.plan.widths:
            bk = self.plan.buckets[wd]
            w = self._weights(self._take(bk.src), self._take(bk.wgt),
                              self._take(bk.epos))
            na = na.at[self._take(bk.rows_local)].set(
                jnp.sum((w > 0).astype(jnp.float32), axis=1))
        return na[:r]

    def unflatten(self, out):
        return self._unflatten(out)
