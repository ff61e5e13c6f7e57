"""The benchmark's side of the program: hand the program a seeded world
and weights, build the one `Experiment` a cell times, take its first
calls in set-up, and time the window.

The window drives `Experiment.run(rounds=R, eval_every=R)` with the fused
schedule: one jitted `lax.scan` program per call, evals at rounds 0 and
R-1.  The loop is closed with one client: each call is dispatched once
the previous call's results are on the host, which `run()` itself does.
"""
from __future__ import annotations

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from bench.reference import change_norms, leaf_norms


def seed_key(seed: int):
    """A PRNG key from a seed of any size (32-bit halves folded in)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def placement(traffic, chips: int):
    """Where the program keeps the node axis: over a mesh of the first
    `chips` devices under the shard_map backend, else on the default
    device (None)."""
    if traffic["backend"] != "shard_map":
        return None
    from repro.dist.sharding import NODE_AXIS, make_mesh

    mesh = make_mesh((chips,), (NODE_AXIS,), devices=jax.devices()[:chips])
    return NamedSharding(mesh, P(NODE_AXIS))


def make_params(model, cfg, seed: int, n: int, dtype=jnp.float32,
                sharding=None):
    """Every node's own weights, made on the device in one jitted call,
    straight into `sharding` where one is given."""
    def build(key):
        return jax.vmap(lambda k: model.init(k, cfg, dtype))(
            jax.random.split(key, n))

    if sharding is None:
        return jax.jit(build)(seed_key(seed))
    return jax.jit(build, out_shardings=sharding)(seed_key(seed))


def program_world(model, cfg, world):
    """The program's `World` over the benchmark's arrays."""
    from repro.engine import World
    from repro.graphs.topology import Topology

    adj = world.adjacency.astype(np.int8)
    nbr = world.nbr_idx
    topo = Topology(name="bench-erdos-renyi", num_nodes=world.num_nodes,
                    adjacency=adj, weights=(adj != 0).astype(np.float32),
                    neighbor_idx=nbr,
                    neighbor_mask=(nbr >= 0).astype(np.int8),
                    max_degree=int(nbr.shape[1]), connected=True)
    return World(model=model.program_model(cfg), topo=topo, xs=world.xs,
                 ys=world.ys, x_test=world.x_test, y_test=world.y_test)


def build_experiment(model, cfg, traffic, world, params, seed: int,
                     sharding=None):
    """Experiment(...) with the benchmark's weights in place of its own;
    `sharding` is `placement(...)`'s, whose mesh the program runs on."""
    from repro.comm import CommConfig
    from repro.engine import Experiment, Schedule

    meth = cfg["method"]
    r = traffic["rounds_per_call"]
    exp = Experiment(
        program_world(model, cfg, world), meth["name"],
        comm=CommConfig(**traffic["transport"]), backend=traffic["backend"],
        layout=traffic["layout"],
        schedule=Schedule(rounds=r, eval_every=r, mode="fused"),
        steps_per_round=meth["local_steps"], batch_size=meth["batch_size"],
        lr=meth["lr"], momentum=meth["momentum"], beta=meth["beta"],
        s=meth["s"], eval_batch=meth["eval_batch"], seed=seed % (2 ** 31),
        mesh=None if sharding is None else sharding.mesh)
    if (jax.tree.structure(exp.params) != jax.tree.structure(params)
            or any(a.shape != b.shape for a, b in zip(
                jax.tree.leaves(exp.params), jax.tree.leaves(params)))):
        raise ValueError("the program's parameter tree differs from the "
                         "configuration's")
    exp.params = params
    exp.opt_state = exp.optimizer.init(params)
    if exp.transport is not None:
        exp.comm_state = exp.transport.init_state(params)
    return exp


@jax.jit
def _copy(tree):
    return jax.tree.map(lambda a: a + 0, tree)


@jax.jit
def _diff(a, b):
    return jax.tree.map(jnp.subtract, a, b)


def call(exp, rounds):
    """One call of the window: R fused rounds; results are on the host
    when it returns."""
    with jax.profiler.TraceAnnotation("bench.call"):
        hist = exp.run(rounds=rounds, eval_every=rounds)
    return hist


def set_up_calls(exp, rounds: int, calls: int):
    """The first `calls` calls, through the window's own call, with the
    readings that the reference follows.  Where the weights lie over
    several chips, their starting point waits on the host."""
    spread = len(jax.tree.leaves(exp.params)[0].sharding.device_set) > 1
    theta0 = jax.device_get(exp.params) if spread else _copy(exp.params)
    out = {"loss": [], "acc": []}
    for c in range(calls):
        hist = call(exp, rounds)
        if c == 0:
            out["loss0"] = float(np.mean(hist[0].loss_per_node))
            out["mom1"] = leaf_norms(exp.opt_state["momentum"])
        out["loss"].append(float(np.mean(hist[-1].loss_per_node)))
        out["acc"].append(float(np.mean(hist[-1].acc_per_node)))
        out["bytes"] = float(hist[-1].bytes_on_wire)
    out["dparam"] = (change_norms(exp.params, theta0) if spread
                     else leaf_norms(_diff(exp.params, theta0)))
    return out


def window(exp, rounds: int, seconds: float):
    """Calls back to back until `seconds` have passed; (calls, seconds)
    from the first dispatch to the end of the last call."""
    n = 0
    t0 = time.perf_counter()
    while True:
        call(exp, rounds)
        n += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return n, elapsed


def kernel_names(hlo: str):
    """{kernel name: occurrences} of the Pallas kernels in optimized HLO."""
    found = {}
    for m in re.finditer(
            r"%([A-Za-z_]+)(?:\.\d+)? = [^\n]*"
            r"custom_call_target=\"tpu_custom_call\"", hlo):
        found[m.group(1)] = found.get(m.group(1), 0) + 1
    return found
