"""The main-path Pallas kernel compiles for a TPU v5e at the paper's widths.

Interpret mode on the CPU cannot show what Mosaic refuses (rank-1 dots,
blocks that break the (8, 128) tiling, VMEM overflow), so these tests
compile `segment_neighbor_avg_rows` with `interpret=False` for a described
v5e chip — no chip needed — at the paper MLP's and CNN's widths and the
50-node ER p=0.2 graph's receiver count.  Nothing runs.  The compiled
program must hold exactly one `segment_avg` kernel (the benchmark counts
reduces by that name) and no temporary the size of the `[N, K, D]`
neighbour panel, which the kernel gathers from the table instead.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
this module.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ops import segment_neighbor_avg_rows

D = 567_434  # the paper MLP 784-512-256-128-10
D_CNN = 1_199_882  # the paper CNN, FC 9216-128-10
NODES = 50  # ER p=0.2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_reduce(sharding, k, d):
    fn = jax.jit(lambda t, i, w: segment_neighbor_avg_rows(
        t, i, w, interpret=False))
    compiled = fn.lower(_spec((NODES, d), jnp.float32, sharding),
                        _spec((NODES, k), jnp.int32, sharding),
                        _spec((NODES, k), jnp.float32, sharding)).compile()
    kernels = re.findall(r"%([A-Za-z_0-9.]+) = [^\n]*tpu_custom_call",
                         compiled.as_text())
    assert len([n for n in kernels if "segment_avg" in n]) == 1, kernels
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < NODES * k * d * 4, temp


@pytest.mark.parametrize("k", [8, 13, 16, 17, 32])
def test_segment_neighbor_avg_compiles_for_v5e(one_chip, no_persistent_cache,
                                               k):
    """The reduce at every sparse bucket width the paper graph produces,
    the dense layout's max_deg (16 for seed 0), and max_deg values other
    seeds and graphs give (13, 17: not multiples of 8)."""
    _compile_reduce(one_chip, k, D)


def test_segment_neighbor_avg_compiles_at_cnn_width(one_chip,
                                                    no_persistent_cache):
    """The CNN cell's reduce: the dense layout's 16 slots at D_CNN."""
    _compile_reduce(one_chip, 16, D_CNN)
