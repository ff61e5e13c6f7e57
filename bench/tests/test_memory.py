"""The reference holds four LM silos on four chips.  Its call is compiled,
not run, for 4 nodes of a parameter tree shaped like one silo of a
catalog MoE LM, with the node axis over 4 devices (`chips` 4, `node_block`
4), from shapes alone.  On each device the arguments, outputs and
temporaries, less what the donated carry aliases, stay within LIMIT, and
no all-gather is larger than the 4 nodes' copy of the largest leaf: the
model is never gathered whole.

The silo is Laguna-XS.2 (huggingface.co/poolside/Laguna-XS.2) at the
model-configs floors: hidden 2048, one leading dense layer 8192 wide and
one 4-layer MoE period holding 8 of the 256 experts (512 wide, a shared
one beside them, the router over all 256), 48 query and 8 KV heads of 128
on every layer, an eighth of the vocabulary (12,544) in the embedding and
the head, norms left out: 363,855,872 parameters.  Its loss is quadratic
in the weights, so what is measured is the harness's own share of the
memory and not a model's activations.

One child process compiles it twice: on 4 virtual CPU devices, under
XLA's memory-minimising CPU schedule (the default one starts every
collective as early as it can, and so holds every leaf's gathered copy at
once, which a TPU schedule does not), and for a described v5e 2x2 where
one can be described.  With the model concatenated to [N, D] and that
gathered whole, these read 17.5 GB and 16.0 GB a device."""
import json
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NODES = 4
LIMIT = 6.5e9  # bytes a device; the one-pass leaf form reads 7.0e9 (CPU)
HIDDEN, VOCAB, DENSE, EXPERTS, EXPERT = 2048, 12544, 8192, 8, 512


def silo_shapes():
    """{name: shape} of one silo's weights (see the module docstring)."""
    h = HIDDEN

    def attention():
        return {"q": (h, 6144), "k": (h, 1024), "v": (h, 1024),
                "o": (6144, h)}

    tree = {"emb": (VOCAB, h), "head": (h, VOCAB),
            "l0": {"attn": attention(),
                   "mlp": {"gate": (h, DENSE), "up": (h, DENSE),
                           "down": (DENSE, h)}}}
    for i in range(1, 5):
        tree[f"l{i}"] = {"attn": attention(), "moe": {
            "router": (h, 256), "gate": (EXPERTS, h, EXPERT),
            "up": (EXPERTS, h, EXPERT), "down": (EXPERTS, EXPERT, h),
            "shared_gate": (h, EXPERT), "shared_up": (h, EXPERT),
            "shared_down": (EXPERT, h)}}
    return tree


class Quadratic:
    """A loss and a score that cost next to nothing beside the weights."""

    @staticmethod
    def loss(params, x, y, cfg):
        c = jnp.mean(x.astype(jnp.float32))
        return sum(jnp.mean(jnp.square(l - c))
                   for l in jax.tree.leaves(params))

    @staticmethod
    def score(params, x, y, cfg):
        c = sum(jnp.mean(l) for l in jax.tree.leaves(params))
        return c * jnp.sum(x.astype(jnp.float32)), c, y.size


def _largest_gather(hlo: str) -> int:
    """Elements of the largest array an all-gather in `hlo` makes."""
    most = 0
    for m in re.finditer(r"= ([^\n]*?) all-gather(?:-start)?\(", hlo):
        for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1)):
            most = max(most, math.prod(int(d) for d in dims.split(",") if d))
    return most


def footprint(devices):
    """The reference call's bytes a device and its gathers, compiled for
    `devices` from shapes alone."""
    from bench.reference import make_reference

    mesh = Mesh(np.array(devices[:NODES]), ("nodes",))
    nodes, every = NamedSharding(mesh, P("nodes")), NamedSharding(mesh, P())

    def shaped(shape, dtype=jnp.float32, sharding=nodes):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree.map(lambda s: shaped((NODES,) + s), silo_shapes(),
                          is_leaf=lambda s: isinstance(s, tuple))
    data = (shaped((NODES, 64, 8), jnp.int32),
            shaped((NODES, 64), jnp.int32), shaped((NODES,), jnp.int32),
            shaped((NODES, NODES), sharding=every),
            shaped((32, 8), jnp.int32, every),
            shaped((32,), jnp.int32, every))
    cfg = {"method": {"batch_size": 2, "local_steps": 1, "lr": 0.01,
                      "momentum": 0.9, "s": 1.0, "eval_batch": 16},
           "reference": {"node_block": NODES, "eval_block": 16}}
    compiled = make_reference(Quadratic, cfg, 2, chips=NODES).lower(
        params, params, data).compile()
    mem = compiled.memory_analysis()
    leaves = jax.tree.leaves(params)
    return {
        "bytes": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes - mem.alias_size_in_bytes),
        "params_per_node": sum(int(np.prod(a.shape[1:])) for a in leaves),
        "largest_leaf_all_nodes": max(int(np.prod(a.shape)) for a in leaves),
        "largest_gather": _largest_gather(compiled.as_text()),
    }


def child():
    """Runs in the child; prints one JSON line."""
    out = {"cpu": footprint(jax.devices()), "v5e": None}
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: the CPU reading stands
        print(f"no v5e:2x2 described: {e}", file=sys.stderr)
    else:
        out["v5e"] = footprint(topo.devices)
    print("RESULT " + json.dumps(out))


@pytest.fixture(scope="module")
def compiled():
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={NODES} "
               "--xla_cpu_enable_concurrency_optimized_scheduler=false")
    code = ("import sys; sys.path[:0] = [%r, %r]; "
            "from bench.tests import test_memory; test_memory.child()"
            % (ROOT, os.path.join(ROOT, "src")))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def _reading(compiled, where):
    if compiled[where] is None:
        pytest.skip("no v5e:2x2 can be described here")
    return compiled[where]


@pytest.mark.parametrize("where", ["cpu", "v5e"])
def test_reference_holds_a_silo_a_chip(compiled, where):
    got = _reading(compiled, where)
    assert got["params_per_node"] == 363_855_872
    assert got["bytes"] <= LIMIT, got


@pytest.mark.parametrize("where", ["cpu", "v5e"])
def test_reference_never_gathers_the_whole_model(compiled, where):
    got = _reading(compiled, where)
    assert got["largest_gather"] <= got["largest_leaf_all_nodes"], got
