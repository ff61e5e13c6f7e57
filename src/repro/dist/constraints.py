"""`with_sharding_constraint` wrappers used inside model forward passes.

Model code calls these unconditionally; each wrapper resolves the currently
active mesh and becomes a no-op when there is none (bare-CPU tests, the
vmapped simulator) — so a single forward implementation serves eager CPU
execution and the jit-compiled production mesh.

Axes that are absent from the active mesh, and dims that are not divisible by
their axis size, silently drop out of the constraint instead of erroring:
constraints here are hints to GSPMD, not hard requirements.
"""
from __future__ import annotations

import math

import jax
from jax._src import core as _core
from jax._src import mesh as _mesh_lib
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import DATA_AXIS, MODEL_AXIS, NODE_AXIS


def current_mesh():
    """The mesh installed by `with mesh:`, or None outside any mesh context."""
    mesh = _mesh_lib.thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def _manual_axes():
    """Mesh axes currently mapped manually (inside shard_map / named vmap):
    those must not appear in a GSPMD sharding constraint."""
    return set(_core.get_axis_env().axis_sizes)


def _axis(mesh, name: str, dim: int, manual=frozenset()):
    if name in mesh.shape and name not in manual and dim % int(mesh.shape[name]) == 0:
        return name
    return None


def _batch_axes(mesh, dim: int, manual=frozenset()):
    """Data-parallel axes for a batch dim: ("pod", "data") when the pod axis
    exists (multi-pod prefill/serve shards the global batch over both)."""
    axes = [a for a in (NODE_AXIS, DATA_AXIS)
            if a in mesh.shape and a not in manual]
    total = math.prod(int(mesh.shape[a]) for a in axes)
    if axes and dim % total == 0:
        return axes[0] if len(axes) == 1 else tuple(axes)
    return _axis(mesh, DATA_AXIS, dim, manual)


def _constrain(mesh, x, spec):
    if all(s is None for s in spec):
        return x  # nothing left to say (e.g. every axis is manual here)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def constrain_batch(x):
    """Keep dim 0 (batch) sharded over the data-parallel axes."""
    mesh = current_mesh()
    if mesh is None or x.ndim == 0:
        return x
    spec = [None] * x.ndim
    spec[0] = _batch_axes(mesh, x.shape[0], _manual_axes())
    return _constrain(mesh, x, spec)


def constrain_residual(x, kind: str = "batch"):
    """Residual stream [B, S, D]: "batch" shards B over data; "batch_seq"
    additionally shards S over the model axis (sequence parallelism for the
    norm/elementwise segments between matmuls)."""
    mesh = current_mesh()
    if mesh is None or x.ndim < 2:
        return x
    manual = _manual_axes()
    spec = [None] * x.ndim
    spec[0] = _batch_axes(mesh, x.shape[0], manual)
    if kind == "batch_seq" and x.ndim >= 3:
        spec[1] = _axis(mesh, MODEL_AXIS, x.shape[1], manual)
    return _constrain(mesh, x, spec)


def constrain_logits(x):
    """Logits [B, S, V]: batch over data, vocab over model (the unembed
    matmul's natural output sharding — avoids gathering [B, S, V] fp32)."""
    mesh = current_mesh()
    if mesh is None or x.ndim < 2:
        return x
    manual = _manual_axes()
    spec = [None] * x.ndim
    spec[0] = _batch_axes(mesh, x.shape[0], manual)
    spec[-1] = _axis(mesh, MODEL_AXIS, x.shape[-1], manual)
    return _constrain(mesh, x, spec)


def gather_weights(layer_params):
    """ZeRO-3 style: constrain one layer's weights to replicated inside the
    scan body, so GSPMD materializes each layer with a just-in-time
    all-gather instead of keeping full weights resident."""
    mesh = current_mesh()
    if mesh is None:
        return layer_params
    if _manual_axes() >= set(mesh.shape):
        return layer_params  # fully manual block: weights are already local
    return jax.tree.map(
        lambda w: jax.lax.with_sharding_constraint(
            w, NamedSharding(mesh, P(*([None] * w.ndim)))),
        layer_params)
