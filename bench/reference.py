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
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

STRIDE = 7919  # the program's minibatch stride (a prime)
EVAL_BLOCK = 1248  # test samples per reference eval block


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
    x_pad = np.zeros((n, m) + world.xs[0].shape[1:], np.float32)
    y_pad = np.zeros((n, m), np.int32)
    for i, (x, y) in enumerate(zip(world.xs, world.ys)):
        x_pad[i, :len(x)] = x
        y_pad[i, :len(y)] = y
    counts = np.array([len(x) for x in world.xs], np.int32)
    return (jnp.asarray(x_pad, dtype), jnp.asarray(y_pad),
            jnp.asarray(counts))


def mixing_matrix(world):
    """[N, N] rows p_ij = |D_j| / sum_{j in N_i} |D_j| over neighbours."""
    counts = np.array([len(x) for x in world.xs], np.float64)
    w = world.adjacency.astype(np.float64) * counts[None, :]
    return (w / w.sum(axis=1, keepdims=True)).astype(np.float32)


FAULTS = ("half_batch", "no_exchange")


def make_reference(model, cfg, rounds, dtype=jnp.float32, fault=None):
    """Returns `call(params, mom, data)`, jitted: R rounds from (params,
    mom) over `data` (from `reference_data`), giving (params, mom, (eval
    at round 0, eval at round R-1)); an eval is per-node (mean test CE
    loss, accuracy).  The data are arguments, not constants, so the
    compiled program serves every world of the same shapes."""
    meth = cfg["method"]
    ncls = cfg["model"]["num_classes"]
    bs, steps = meth["batch_size"], meth["local_steps"]
    lr, mu = meth["lr"], meth["momentum"]
    beta, s = meth["beta"], meth["s"]
    highest = dtype == jnp.float32

    def node_loss(p, x, y):
        return _vt_loss(model.apply(p, x, cfg), y, beta, ncls)

    grad_all = jax.vmap(jax.value_and_grad(node_loss))

    def call(params, mom, data):
        x_pad, y_pad, counts, mix, x_test, y_test = data
        used = x_test.shape[0]

        def local_step(carry, t):
            params, mom = carry
            idx = ((t * bs + jnp.arange(bs, dtype=jnp.int32))
                   * STRIDE)[None, :] % counts[:, None]
            xb = jnp.take_along_axis(
                x_pad, idx.reshape(idx.shape + (1,) * (x_pad.ndim - 2)),
                axis=1)
            yb = jnp.take_along_axis(y_pad, idx, axis=1)
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

        def evaluate(params):
            def block(p, xy):
                ce, ok = _ce_and_correct(model.apply(p, xy[0], cfg), xy[1],
                                         ncls)
                return (jnp.sum(ce.astype(jnp.float32)),
                        jnp.sum(ok.astype(jnp.float32)))

            def node(p):
                if used % EVAL_BLOCK:
                    ce, ok = block(p, (x_test, y_test))
                else:
                    nb = used // EVAL_BLOCK
                    ce, ok = jax.lax.map(lambda xy: block(p, xy), (
                        x_test.reshape((nb, EVAL_BLOCK) + x_test.shape[1:]),
                        y_test.reshape(nb, EVAL_BLOCK)))
                    ce, ok = jnp.sum(ce), jnp.sum(ok)
                return ce / used, ok / used
            return jax.lax.map(node, params)

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


def reference_data(cfg, world, dtype=jnp.float32):
    """The arrays `make_reference`'s call reads, on the device."""
    meth = cfg["method"]
    x_pad, y_pad, counts = data_arrays(world, dtype)
    used = (len(world.x_test) // meth["eval_batch"]) * meth["eval_batch"]
    return (x_pad, y_pad, counts,
            jnp.asarray(mixing_matrix(world), dtype),
            jnp.asarray(world.x_test[:used], dtype),
            jnp.asarray(world.y_test[:used].astype(np.int32)))


def reference_run(model, cfg, world, params0, rounds, calls,
                  dtype=jnp.float32, fault=None):
    """Run `calls` calls of R rounds from `params0`; the readings the
    harness compares (see `bench/correct.py`)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; faults: {FAULTS}")
    call = make_reference(model, cfg, rounds, dtype, fault)
    data = reference_data(cfg, world, dtype)
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype), params0)
    theta0 = params
    mom = jax.tree.map(jnp.zeros_like, params)
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
