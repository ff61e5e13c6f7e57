"""DecDiff aggregation — the paper's Eq. (5) and Eq. (6).

The two sources of heterogeneity the paper targets (non-IID data and
*uncoordinated model initialization*) make naive coordinate-wise averaging
destructive: after the first exchange, averaging weights that encode different
feature<->parameter assignments wipes out previously learned information
(paper Fig. 1).  DecDiff instead moves the local model toward the
neighbourhood average with a step attenuated by the *global* L2 distance
between the two:

    w_i <- w_i + (w̄_i - w_i) / (||w̄_i - w_i||_2 + s),     s >= 1    (Eq. 5)

    w̄_i = Σ_{j in N_i} ω_ij p_ij w_j / Σ_{j in N_i} ω_ij p_ij       (Eq. 6)

Note the average *excludes* the local model (it is a reference point, not a
replacement), and the norm is computed over the whole flattened model, so the
step size automatically shrinks when models are topologically far apart
(early rounds / heterogeneous init) and grows as they converge.

Everything here operates on pytrees; distances are accumulated leafwise in
fp32.  For sharded (jit/shard_map) execution see `repro.dist.dfl_step`,
which applies the same update over a stacked node axis (vmapped, or
shard_mapped over the pod ring).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.utils.pytree import (
    tree_sq_norm,
    tree_sub,
    tree_weighted_sum,
)

DEFAULT_S = 1.0  # paper: smallest value limiting the denominator's influence.


def neighborhood_average(neighbor_models: Sequence, weights) -> object:
    """Eq. (6): weighted average of the *neighbours'* models.

    Args:
      neighbor_models: list of pytrees, the models received from N_i.
      weights: per-neighbour scalar weights ω_ij * p_ij (any positive scale —
        normalized internally).

    Returns:
      The neighbourhood average model w̄_i (same structure as the inputs).
    """
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.sum(w)
    return tree_weighted_sum(list(neighbor_models), list(w))


def decdiff_step(local_model, avg_model, s: float = DEFAULT_S):
    """Eq. (5): distance-attenuated step from `local_model` toward `avg_model`.

    Returns the updated model.  The step length along (w̄ - w) is
    d / (d + s) < 1 with d = ||w̄ - w||_2, i.e. the update never overshoots
    the average model and vanishes smoothly as d -> 0 or d -> inf... more
    precisely the *relative* step d/(d+s) -> 1 as d -> inf but the *applied*
    scale 1/(d+s) -> 0, which is what bounds disruption for far-apart models.
    """
    diff = tree_sub(avg_model, local_model)
    d = jnp.sqrt(tree_sq_norm(diff))
    scale = 1.0 / (d + s)
    return jax.tree.map(lambda wi, di: (wi + scale * di).astype(wi.dtype), local_model, diff)


def decdiff_aggregate(local_model, neighbor_models: Sequence, weights,
                      s: float = DEFAULT_S):
    """Full DecDiff aggregation: Eq. (6) then Eq. (5).

    This is the function a node runs at each communication round (Alg. 1,
    lines 12-13) after receiving its neighbours' models.
    """
    if len(neighbor_models) == 0:
        return local_model  # isolated this round: keep the local model.
    avg = neighborhood_average(neighbor_models, weights)
    return decdiff_step(local_model, avg, s=s)


def decdiff_aggregate_stacked(local_model, stacked_neighbors, weights, mask=None,
                              s: float = DEFAULT_S):
    """Vectorized variant: neighbours stacked along a leading axis.

    Args:
      local_model: pytree with leaves of shape [...].
      stacked_neighbors: pytree with leaves of shape [N, ...].
      weights: [N] float weights (ω_ij p_ij).
      mask: optional [N] {0,1} — masks out neighbours that did not deliver a
        model this round (the paper does not impose synchronization; a node
        may hear from only a fraction of N_i).

    Used by the vmapped multi-node simulator where all nodes' neighbour sets
    are padded to the max degree.
    """
    w = jnp.asarray(weights, jnp.float32)
    if mask is not None:
        w = w * jnp.asarray(mask, jnp.float32)
    total = jnp.sum(w)
    # If no neighbour delivered, fall back to the local model (scale -> 0).
    safe_total = jnp.where(total > 0, total, 1.0)
    wn = w / safe_total

    def avg_leaf(stacked):
        return jnp.tensordot(wn, stacked.astype(jnp.float32), axes=(0, 0))

    avg = jax.tree.map(avg_leaf, stacked_neighbors)
    diff = jax.tree.map(lambda a, l: a - l.astype(jnp.float32), avg, local_model)
    d = jnp.sqrt(tree_sq_norm(diff))
    scale = jnp.where(total > 0, 1.0 / (d + s), 0.0)
    return jax.tree.map(
        lambda wi, di: (wi.astype(jnp.float32) + scale * di).astype(wi.dtype),
        local_model, diff,
    )
