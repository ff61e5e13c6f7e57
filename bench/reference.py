"""Plain reference of the benchmark's timed path: DecDiff + virtual teacher
with per-node gossip of full float32 models, written from the paper and
independent of the program under test.

Per round (arXiv 2312.04504, Alg. 1): every node takes `local_steps`
SGD-momentum steps on the virtual-teacher loss (Eq. 7-8) over minibatches
of its own shard; then every node moves toward the average of its
neighbours' models, weighted by their data sizes (Eq. 6), by the distance-
attenuated DecDiff step (Eq. 5).  Every model reaches every neighbour
every round.  The minibatch of step t on a node holding c samples is its
samples (t*B + 0..B-1) * 7919 mod c, and each call of R rounds restarts t
at 0 (the program's documented batch schedule).  A node is scored on the
first `eval_batch * floor(test / eval_batch)` test samples after rounds 0
and R-1 of a call.

In float32 every matmul and convolution runs at HIGHEST precision.  With
`dtype=bfloat16` the same algorithm runs with parameters, optimizer state,
data and arithmetic in bfloat16: the lower-precision control.  `fault`
plants one of the faults the comparison must catch: "half_batch" (each
step's loss is the mean over the first half of its minibatch) or
"no_exchange" (the gossip step is left out).

Nothing here imports the program.  Memory: the [N, N] @ [N, D] neighbour
average and per-node evals in blocks of samples, so that the paper's
50 x 1.2M-parameter CNN fits one chip.

What depends on the model comes from the configuration's module
(`bench/configs/<config>.py`), each part with a default, the paper's
classifier:

  loss(params, x, y, cfg)   one node's training loss on one minibatch
                            (default: the virtual-teacher KL of
                            `apply`'s logits over `model.num_classes`)
  score(params, x, y, cfg)  (CE summed, correct summed, labels counted)
                            over one eval block; labels counted is a
                            number known from the shapes (default: the
                            classifier's, one label a sample)

and from the configuration's `reference` block, every key optional:

  eval_block  test samples a node scores at a time (default 1248)
  node_block  nodes vmapped together in the local step and the eval,
              one group after another (default: the local step vmaps
              every node, the eval scores one node at a time)

A node's eval loss is CE over labels counted, its accuracy correct over
labels counted.  Integer inputs (token ids) and labels keep their type in
every precision.  With `chips` > 1 the node axis of the parameters, the
momentum and the data shards lies over the first `chips` devices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

STRIDE = 7919  # the program's minibatch stride (a prime)
EVAL_BLOCK = 1248  # test samples per reference eval block, by default
NODES = "nodes"  # the mesh axis the node axis lies on with chips > 1


def _vt_loss(logits, labels, beta, num_classes):
    """KL(p_t || softmax(z)) with p_t = beta on the label, the rest even."""
    a = (1.0 - beta) / (num_classes - 1)
    onehot = jax.nn.one_hot(labels, num_classes, dtype=logits.dtype)
    p_t = onehot * beta + (1.0 - onehot) * a
    logp = jax.nn.log_softmax(logits, axis=-1)
    kl = jnp.sum(p_t * (jnp.log(p_t) - logp), axis=-1)
    return jnp.mean(kl)


def _ce_and_correct(logits, labels, num_classes):
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.sum(jax.nn.one_hot(labels, num_classes, dtype=logp.dtype)
                  * logp, axis=-1)
    return ce, (jnp.argmax(logits, axis=-1) == labels)


def hooks(model):
    """(loss, score) of a configuration's module, each defaulting to the
    paper's classifier (see the module docstring)."""
    def vt_loss(params, x, y, cfg):
        return _vt_loss(model.apply(params, x, cfg), y,
                        cfg["method"]["beta"], cfg["model"]["num_classes"])

    def ce_score(params, x, y, cfg):
        ce, ok = _ce_and_correct(model.apply(params, x, cfg), y,
                                 cfg["model"]["num_classes"])
        return (jnp.sum(ce.astype(jnp.float32)),
                jnp.sum(ok.astype(jnp.float32)), y.size)

    return (getattr(model, "loss", vt_loss),
            getattr(model, "score", ce_score))


def _is_float(a) -> bool:
    return np.issubdtype(a.dtype, np.floating)


def _cast(a, dtype):
    """`a` on the device, floats in `dtype`; integers keep their type."""
    return jnp.asarray(a, dtype if _is_float(a) else None)


@jax.jit
def _norms(tree):
    return jax.tree.map(
        lambda v: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))), tree)


def leaf_norms(tree):
    """{path: float} Frobenius norm of each leaf (over all nodes)."""
    flat = jax.tree_util.tree_flatten_with_path(
        jax.device_get(_norms(tree)))[0]
    return {jax.tree_util.keystr(k): float(v) for k, v in flat}


def data_arrays(world, dtype):
    """Per-node shards padded to a common length (padding never read)."""
    n = world.num_nodes
    m = max(len(x) for x in world.xs)
    x0 = world.xs[0]
    x_pad = np.zeros((n, m) + x0.shape[1:],
                     np.float32 if _is_float(x0) else x0.dtype)
    y_pad = np.zeros((n, m) + world.ys[0].shape[1:], np.int32)
    for i, (x, y) in enumerate(zip(world.xs, world.ys)):
        x_pad[i, :len(x)] = x
        y_pad[i, :len(y)] = y
    counts = np.array([len(x) for x in world.xs], np.int32)
    return (_cast(x_pad, dtype), jnp.asarray(y_pad), jnp.asarray(counts))


def mixing_matrix(world):
    """[N, N] rows p_ij = |D_j| / sum_{j in N_i} |D_j| over neighbours."""
    counts = np.array([len(x) for x in world.xs], np.float64)
    w = world.adjacency.astype(np.float64) * counts[None, :]
    return (w / w.sum(axis=1, keepdims=True)).astype(np.float32)


FAULTS = ("half_batch", "no_exchange")


def _in_groups(fn, args, k):
    """`fn` over the node axis of `args` in groups of `k` nodes, one group
    after another.  Group g holds nodes g, g + N/k, g + 2N/k, ..., so that
    with the node axis laid over the chips in equal blocks, and `k` a
    multiple of their number, every group spans every chip."""
    n = jax.tree.leaves(args)[0].shape[0]
    if n % k:
        raise ValueError(f"node_block {k} does not divide {n} nodes")
    g = n // k
    out = jax.lax.map(fn, jax.tree.map(
        lambda a: jnp.swapaxes(a.reshape((k, g) + a.shape[1:]), 0, 1),
        args))
    return jax.tree.map(
        lambda a: jnp.swapaxes(a, 0, 1).reshape((n,) + a.shape[2:]), out)


def _score_all(score, p, x, y, cfg, block):
    """(CE summed, correct summed, labels counted) of one node over the
    test set, `block` samples at a time where they tile it."""
    if x.shape[0] % block:
        return score(p, x, y, cfg)
    nb = x.shape[0] // block
    labels = []

    def one(xy):
        ce, ok, count = score(p, xy[0], xy[1], cfg)
        labels.append(count)
        return ce, ok

    ce, ok = jax.lax.map(one, (x.reshape((nb, block) + x.shape[1:]),
                               y.reshape((nb, block) + y.shape[1:])))
    return jnp.sum(ce), jnp.sum(ok), nb * labels[0]


def make_reference(model, cfg, rounds, dtype=jnp.float32, fault=None):
    """Returns `call(params, mom, data)`, jitted: R rounds from (params,
    mom) over `data` (from `reference_data`), giving (params, mom, (eval
    at round 0, eval at round R-1)); an eval is per-node (mean test CE
    loss, accuracy).  The data are arguments, not constants, so the
    compiled program serves every world of the same shapes."""
    meth = cfg["method"]
    opts = cfg.get("reference", {})
    eval_block = opts.get("eval_block", EVAL_BLOCK)
    node_block = opts.get("node_block")
    bs, steps = meth["batch_size"], meth["local_steps"]
    lr, mu = meth["lr"], meth["momentum"]
    s = meth["s"]
    highest = dtype == jnp.float32
    loss, score = hooks(model)

    def node_loss(p, x, y):
        return loss(p, x, y, cfg)

    grad_all = jax.vmap(jax.value_and_grad(node_loss))
    if node_block is not None:
        vmapped = grad_all

        def grad_all(*a):
            return _in_groups(lambda g: vmapped(*g), a, node_block)

    def call(params, mom, data):
        x_pad, y_pad, counts, mix, x_test, y_test = data

        def local_step(carry, t):
            params, mom = carry
            idx = ((t * bs + jnp.arange(bs, dtype=jnp.int32))
                   * STRIDE)[None, :] % counts[:, None]
            xb = jnp.take_along_axis(
                x_pad, idx.reshape(idx.shape + (1,) * (x_pad.ndim - 2)),
                axis=1)
            yb = jnp.take_along_axis(
                y_pad, idx.reshape(idx.shape + (1,) * (y_pad.ndim - 2)),
                axis=1)
            if fault == "half_batch":
                xb, yb = xb[:, :bs // 2], yb[:, :bs // 2]
            _, g = grad_all(params, xb, yb)
            mom = jax.tree.map(lambda v, gi: (mu * v + gi).astype(dtype),
                               mom, g)
            params = jax.tree.map(lambda p, v: (p - lr * v).astype(dtype),
                                  params, mom)
            return (params, mom), None

        def decdiff(params):
            leaves, tree = jax.tree.flatten(params)
            n = leaves[0].shape[0]
            flat = jnp.concatenate([l.reshape(n, -1) for l in leaves],
                                   axis=1)
            diff = mix @ flat - flat
            d = jnp.sqrt(jnp.sum(jnp.square(diff), axis=1, keepdims=True))
            flat = (flat + diff / (d + s)).astype(dtype)
            out, off = [], 0
            for l in leaves:
                size = int(np.prod(l.shape[1:]))
                out.append(flat[:, off:off + size].reshape(l.shape))
                off += size
            return jax.tree.unflatten(tree, out)

        def one_round(carry, r):
            params, mom = carry
            (params, mom), _ = jax.lax.scan(
                local_step, (params, mom), r * steps + jnp.arange(steps))
            if fault == "no_exchange":
                return (params, mom), None
            return (decdiff(params), mom), None

        def node(p):
            ce, ok, labels = _score_all(score, p, x_test, y_test, cfg,
                                        eval_block)
            return ce / labels, ok / labels

        def evaluate(params):
            if node_block is None:
                return jax.lax.map(node, params)
            return _in_groups(jax.vmap(node), params, node_block)

        (params, mom), _ = one_round((params, mom), 0)
        first = evaluate(params)
        (params, mom), _ = jax.lax.scan(one_round, (params, mom),
                                        jnp.arange(1, rounds))
        return params, mom, (first, evaluate(params))

    def wrapped(*a):
        if highest:
            with jax.default_matmul_precision("highest"):
                return call(*a)
        return call(*a)

    return jax.jit(wrapped)


def node_sharding(chips: int):
    """Where the reference lays the node axis: nowhere in particular on
    one chip, else in equal blocks over the first `chips` devices."""
    if chips == 1:
        return None
    mesh = Mesh(np.array(jax.devices()[:chips]), (NODES,))
    return NamedSharding(mesh, P(NODES))


def reference_data(cfg, world, dtype=jnp.float32, chips=1):
    """The arrays `make_reference`'s call reads, on the device: the
    per-node shards on the node axis's chips, the rest on each of them."""
    meth = cfg["method"]
    x_pad, y_pad, counts = data_arrays(world, dtype)
    used = (len(world.x_test) // meth["eval_batch"]) * meth["eval_batch"]
    data = (x_pad, y_pad, counts,
            jnp.asarray(mixing_matrix(world), dtype),
            _cast(world.x_test[:used], dtype),
            jnp.asarray(world.y_test[:used].astype(np.int32)))
    nodes = node_sharding(chips)
    if nodes is None:
        return data
    every = NamedSharding(nodes.mesh, P())
    return (jax.device_put(data[:3], nodes)
            + jax.device_put(data[3:], every))


def reference_run(model, cfg, world, params0, rounds, calls,
                  dtype=jnp.float32, fault=None, chips=1):
    """Run `calls` calls of R rounds from `params0`; the readings the
    harness compares (see `bench/correct.py`)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; faults: {FAULTS}")
    call = make_reference(model, cfg, rounds, dtype, fault)
    data = reference_data(cfg, world, dtype, chips)
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype), params0)
    mom = jax.tree.map(jnp.zeros_like, params)
    nodes = node_sharding(chips)
    if nodes is not None:
        params, mom = jax.device_put((params, mom), nodes)
    theta0 = params
    out = {"loss": [], "acc": []}
    for c in range(calls):
        params, mom, (first, last) = call(params, mom, data)
        if c == 0:
            out["loss0"] = float(jnp.mean(first[0].astype(jnp.float32)))
            out["mom1"] = leaf_norms(mom)
        out["loss"].append(float(jnp.mean(last[0].astype(jnp.float32))))
        out["acc"].append(float(jnp.mean(last[1].astype(jnp.float32))))
    out["dparam"] = leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        params, theta0))
    return out
