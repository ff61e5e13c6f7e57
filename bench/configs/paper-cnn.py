"""paper-cnn: the paper's Fashion-MNIST model.

Conv 3x3 (32) -> ReLU -> conv 3x3 (64) -> ReLU -> max-pool 2 -> FC 9216-128
-> ReLU -> FC 128-10, VALID convolutions, NHWC.  Beside `paper-cnn.json`
(the sizes as run) this file holds the plain reference of the model, its
operation counts, and the function that hands the program the same model.
Parameter names follow the program's pytree (`conv0`, `conv1`, `fc0`,
`fc1`; conv weights HWIO).
"""
import math

import jax
import jax.numpy as jnp


def _shapes(cfg):
    m = cfg["model"]
    h, w = m["input_hw"]
    k = m["kernel"]
    c0, c1 = m["conv_channels"]
    oh, ow = h - 2 * (k - 1), w - 2 * (k - 1)
    flat = (oh // m["pool"]) * (ow // m["pool"]) * c1
    return {"conv0": ((k, k, 1, c0), k * k * 1),
            "conv1": ((k, k, c0, c1), k * k * c0),
            "fc0": ((flat, m["hidden"][0]), flat),
            "fc1": ((m["hidden"][0], m["num_classes"]), m["hidden"][0])}


def init(key, cfg, dtype=jnp.float32):
    """One node's weights: uniform(-1/sqrt(fan_in), 1/sqrt(fan_in))."""
    shapes = _shapes(cfg)
    params = {}
    for (name, (shape, fan_in)), k in zip(
            shapes.items(), jax.random.split(key, len(shapes))):
        kw, kb = jax.random.split(k)
        bound = 1.0 / math.sqrt(fan_in)
        params[name] = {
            "w": jax.random.uniform(kw, shape, jnp.float32, -bound,
                                    bound).astype(dtype),
            "b": jax.random.uniform(kb, (shape[-1],), jnp.float32, -bound,
                                    bound).astype(dtype)}
    return params


def _conv(x, p):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def apply(params, x, cfg):
    """Logits [B, classes] of images [B, H, W]."""
    pool = cfg["model"]["pool"]
    h = jax.nn.relu(_conv(x[..., None], params["conv0"]))
    h = jax.nn.relu(_conv(h, params["conv1"]))
    b, hh, ww, c = h.shape
    h = h.reshape(b, hh // pool, pool, ww // pool, pool, c).max(axis=(2, 4))
    h = h.reshape(b, -1)
    h = jax.nn.relu(jnp.dot(h, params["fc0"]["w"]) + params["fc0"]["b"])
    return jnp.dot(h, params["fc1"]["w"]) + params["fc1"]["b"]


def macs_per_sample(cfg) -> int:
    """Multiply-accumulates of one forward pass of one sample."""
    m = cfg["model"]
    h, w = m["input_hw"]
    k = m["kernel"]
    c0, c1 = m["conv_channels"]
    o0h, o0w = h - k + 1, w - k + 1
    o1h, o1w = o0h - k + 1, o0w - k + 1
    flat = (o1h // m["pool"]) * (o1w // m["pool"]) * c1
    return (o0h * o0w * c0 * k * k * 1 + o1h * o1w * c1 * k * k * c0
            + flat * m["hidden"][0] + m["hidden"][0] * m["num_classes"])


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
    return sum(math.prod(shape) + shape[-1]
               for shape, _ in _shapes(cfg).values())


def program_model(cfg):
    """The program's model object for this configuration."""
    from repro.models.mlp_cnn import make_cnn

    m = cfg["model"]
    return make_cnn(num_classes=m["num_classes"],
                    in_hw=tuple(m["input_hw"]), use_pool_dropout=False)
