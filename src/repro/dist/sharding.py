"""PartitionSpec inference for a ("data", "model") device mesh.

Mesh convention:

  * "data"  — batch / data parallelism,
  * "model" — tensor parallelism (weights and feature dims),
  * "pod"   — optional leading axis carrying the DFL node dimension: one
              decentralized-learning participant per pod.

Specs are inferred per leaf from shape + dtype alone, so the same rules cover
every architecture family without per-model sharding tables:

  * integer/bool leaves replicate (token ids, slot maps, counters),
  * small leaves replicate (norm scales, biases — sharding them buys nothing
    and forces collectives on every use),
  * leading stack dims (scan-over-layers [L, ...] leaves, the DFL node dim)
    are never sharded over "data"/"model"; the node dim maps to "pod",
  * of the remaining dims, the largest dim divisible by the axis size goes to
    "model", the largest other divisible dim to "data"; non-divisible dims
    stay unsharded rather than forcing padding.
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
NODE_AXIS = "pod"

# Leaves with fewer elements than this (ignoring reserved leading dims)
# replicate: at bf16 this is a 128 KiB ceiling, well under one DMA's worth.
SMALL_LEAF_ELEMS = 1 << 16

# Keys whose subtrees carry stacked per-layer params with this many leading
# stack dims ([L, ...] from vmapped init; zamba's mamba blocks are [G, E, ...]).
_STACK_LEAD = {"layers": 1, "enc_layers": 1, "dec_layers": 1, "mamba": 2}


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """`jax.make_mesh` with every axis `Auto`.

    The engines place data through shard_map specs and jit propagation, not
    through sharding-in-types; on an `Explicit` axis (jax.make_mesh's
    default) the pod-sharded params a shard_map round returns cannot enter
    a jit traced outside the mesh, e.g. the per-node eval."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def auto_mesh(mesh: Mesh) -> Mesh:
    """The same devices and axis names as `mesh`, every axis `Auto`."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def _axis_size(mesh, name: str) -> int:
    return int(mesh.shape[name]) if name in mesh.shape else 1


def _replicated(dtype) -> bool:
    return np.dtype(dtype).kind in "iub"


def leaf_spec(shape, dtype, n_lead, data_axis, model_axis, mesh):
    """Infer the PartitionSpec for one leaf.

    Args:
      shape, dtype: the leaf's shape and dtype.
      n_lead: number of leading stack dims that must stay unsharded here
        (layer-scan dims, the DFL node dim — the caller owns those).
      data_axis, model_axis: mesh axis names.
      mesh: anything with a `.shape` mapping axis name -> size.
    """
    shape = tuple(int(d) for d in shape)
    rank = len(shape)
    spec = [None] * rank
    if rank == 0 or rank <= n_lead or _replicated(dtype):
        return P(*spec)
    if math.prod(shape[n_lead:]) < SMALL_LEAF_ELEMS:
        return P(*spec)
    by_size = sorted(range(n_lead, rank), key=lambda i: (-shape[i], i))
    model_n = _axis_size(mesh, model_axis)
    model_dim = next((i for i in by_size if shape[i] % model_n == 0), None)
    if model_dim is not None:
        spec[model_dim] = model_axis
    data_n = _axis_size(mesh, data_axis)
    data_dim = next(
        (i for i in by_size if i != model_dim and shape[i] % data_n == 0), None
    )
    if data_dim is not None:
        spec[data_dim] = data_axis
    return P(*spec)


def _path_keys(path):
    keys = []
    for entry in path:
        key = getattr(entry, "key", None)
        if key is None:
            key = getattr(entry, "name", None)
        if key is None:
            key = getattr(entry, "idx", None)
        keys.append(str(key))
    return keys


def make_param_specs(params, mesh, *, dfl_node_axis: bool = False):
    """PartitionSpecs for a parameter pytree (same structure, P leaves).

    With `dfl_node_axis=True` every leaf carries a leading per-node stack dim
    (one model per DFL participant) which maps to the "pod" mesh axis.
    """
    pod_n = _axis_size(mesh, NODE_AXIS)

    def one(path, leaf):
        keys = _path_keys(path)
        n_stack = max((_STACK_LEAD.get(k, 0) for k in keys), default=0)
        n_lead = int(dfl_node_axis) + n_stack
        shape = tuple(int(d) for d in leaf.shape)
        spec = list(leaf_spec(shape, leaf.dtype, n_lead,
                              DATA_AXIS, MODEL_AXIS, mesh))
        if (dfl_node_axis and shape and NODE_AXIS in mesh.shape
                and shape[0] % pod_n == 0):
            spec[0] = NODE_AXIS
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, params)


def make_batch_specs(batch, mesh, *, dfl_node_axis: bool = False,
                     dp_axes=(DATA_AXIS,)):
    """PartitionSpecs for input batches: the batch dim shards over `dp_axes`
    (e.g. ("pod", "data") for multi-pod prefill), everything else replicates.
    With `dfl_node_axis=True` dim 0 is the per-node stack dim -> "pod"."""
    total = math.prod(_axis_size(mesh, a) for a in dp_axes)

    def one(leaf):
        shape = tuple(int(d) for d in leaf.shape)
        rank = len(shape)
        spec = [None] * rank
        b_dim = 0
        if dfl_node_axis:
            if (rank and NODE_AXIS in mesh.shape
                    and shape[0] % _axis_size(mesh, NODE_AXIS) == 0):
                spec[0] = NODE_AXIS
            b_dim = 1
        if rank > b_dim and shape[b_dim] % total == 0:
            spec[b_dim] = dp_axes[0] if len(dp_axes) == 1 else tuple(dp_axes)
        return P(*spec)

    return jax.tree.map(one, batch)


def make_cache_specs(cache, mesh):
    """PartitionSpecs for decode caches.

    KV caches are [L, B, W, H, hd] (ring-buffer window W); SSM states are
    [L, B, ...].  The layer-stack dim and the window dim never shard (decode
    writes one slot per step — sharding W would turn every write into a
    collective); batch -> "data", and the largest divisible trailing feature
    dim (head_dim, conv channels, state) -> "model".  Integer leaves
    (slot_pos, length) replicate.
    """

    def one(leaf):
        shape = tuple(int(d) for d in leaf.shape)
        rank = len(shape)
        spec = [None] * rank
        if rank < 2 or _replicated(leaf.dtype):
            return P(*spec)
        if shape[1] % _axis_size(mesh, DATA_AXIS) == 0:
            spec[1] = DATA_AXIS
        model_n = _axis_size(mesh, MODEL_AXIS)
        first_feature = 3 if rank >= 4 else 2
        for i in range(rank - 1, first_feature - 1, -1):
            if shape[i] % model_n == 0:
                spec[i] = MODEL_AXIS
                break
        return P(*spec)

    return jax.tree.map(one, cache)


def named(specs, mesh):
    """Wrap a pytree of PartitionSpecs into NamedShardings for jit
    in_shardings/out_shardings."""
    return jax.tree.map(
        lambda s: s if isinstance(s, NamedSharding) else NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, (P, NamedSharding)),
    )
