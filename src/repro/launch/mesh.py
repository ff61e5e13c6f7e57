"""Production mesh definitions (TPU v5e target).

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16); the `pod` axis
carries the DFL node dim (one decentralized-learning participant per pod).

Functions, not module constants — importing this module must never touch jax
device state (the dry-run sets XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import jax

from repro.dist.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, data: int = 1, model: int = 1):
    """Tiny mesh on the real local devices (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    return make_mesh((data, max(1, min(model, n // data))), ("data", "model"))


HW = dict(  # TPU v5e constants used by the roofline analysis
    peak_flops_bf16=197e12,  # per chip
    hbm_bw=819e9,  # bytes/s per chip
    ici_bw=50e9,  # bytes/s per link (~per chip usable)
    hbm_bytes=16e9,
)
