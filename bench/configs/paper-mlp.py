"""paper-mlp: the paper's MNIST model, MLP 784-512-256-128-10 with ReLU.

Beside `paper-mlp.json` (the sizes as run) this file holds the plain
reference of the model, its operation counts, and the function that hands
the program the same model.  Parameter names follow the program's pytree
(`fc0` .. `fc3`, each `{"w": [in, out], "b": [out]}`), so the benchmark
can hand the program the weights it makes and compare leaf by leaf.
"""
import math

import jax
import jax.numpy as jnp


def _dims(cfg):
    m = cfg["model"]
    h, w = m["input_hw"]
    return [h * w, *m["hidden"], m["num_classes"]]


def init(key, cfg, dtype=jnp.float32):
    """One node's weights: uniform(-1/sqrt(fan_in), 1/sqrt(fan_in))."""
    dims = _dims(cfg)
    params = {}
    for i, k in enumerate(jax.random.split(key, len(dims) - 1)):
        kw, kb = jax.random.split(k)
        bound = 1.0 / math.sqrt(dims[i])
        params[f"fc{i}"] = {
            "w": jax.random.uniform(kw, (dims[i], dims[i + 1]), jnp.float32,
                                    -bound, bound).astype(dtype),
            "b": jax.random.uniform(kb, (dims[i + 1],), jnp.float32,
                                    -bound, bound).astype(dtype)}
    return params


def apply(params, x, cfg):
    """Logits [B, classes] of images [B, H, W]."""
    n_layers = len(_dims(cfg)) - 1
    h = x.reshape(x.shape[0], -1)
    for i in range(n_layers):
        p = params[f"fc{i}"]
        h = jnp.dot(h, p["w"]) + p["b"]
        if i < n_layers - 1:
            h = jax.nn.relu(h)
    return h


def macs_per_sample(cfg) -> int:
    """Multiply-accumulates of one forward pass of one sample."""
    dims = _dims(cfg)
    return sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def flops_per_call(cfg, nodes: int, rounds: int, evals: int,
                   eval_samples: int) -> float:
    """Model FLOPs of one call of R rounds: every node's local steps
    (forward and backward, 3 x 2 x MACs per sample) and `evals` scorings
    of `eval_samples` test samples on every node (2 x MACs per sample)."""
    meth = cfg["method"]
    macs = macs_per_sample(cfg)
    train = 6 * macs * meth["batch_size"] * meth["local_steps"] * nodes
    return float(train * rounds + 2 * macs * eval_samples * nodes * evals)


def param_count(cfg) -> int:
    dims = _dims(cfg)
    return sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))


def program_model(cfg):
    """The program's model object for this configuration."""
    from repro.models.mlp_cnn import make_mlp

    m = cfg["model"]
    h, w = m["input_hw"]
    return make_mlp(num_classes=m["num_classes"], input_dim=h * w,
                    hidden=tuple(m["hidden"]))
