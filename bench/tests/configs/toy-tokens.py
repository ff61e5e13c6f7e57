"""toy-tokens: a token world and a last-token model, through the hooks.

Each node holds sequences of `seq_len` token ids drawn from a Zipf law
over the vocabulary (the world block's `vocab`, which the model shares),
with a skew and a ranking of the ids of its own; the label of a sequence
is the token that follows it.  The test set draws equally from every
node's law.  The model embeds the ids, averages over
the positions and maps the average to logits over the vocabulary:
embedding -> mean -> dense -> [B, V].

Beside the plain reference (`init`, `apply`) this file gives the hooks
the harness takes from a configuration: `build_world`, `loss` (the
virtual-teacher KL in closed form, with no [B, V] teacher) and `score`,
and hands the program the same model (`program_model`).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np


def _sequences(rng, law, count, length):
    return rng.choice(law.size, size=(count, length), p=law).astype(np.int32)


def build_world(world_cfg):
    """Per-node token shards of uneven sizes, the test set, the ER graph."""
    from bench.world import PaperWorld, erdos_renyi, padded_neighbors

    seed, n, v = world_cfg["seed"], world_cfg["nodes"], world_cfg["vocab"]
    length = world_cfg["seq_len"] + 1
    rng = np.random.default_rng(seed)
    laws = []
    for skew in np.linspace(*world_cfg["zipf_skew"], n):
        weights = np.empty(v)
        weights[rng.permutation(v)] = 1.0 / np.arange(1, v + 1) ** skew
        laws.append(weights / weights.sum())
    shares = 1.0 / np.arange(1, n + 1) ** 0.8
    sizes = np.maximum((shares / shares.sum()
                        * world_cfg["train_size"]).astype(int), 16)
    train = [_sequences(rng, law, int(c), length)
             for law, c in zip(laws, sizes)]
    test = np.concatenate([
        _sequences(rng, law, world_cfg["test_size"] // n, length)
        for law in laws])
    adj = erdos_renyi(n, world_cfg["er_p"], seed)
    return PaperWorld(xs=[t[:, :-1] for t in train],
                      ys=[t[:, -1] for t in train], x_test=test[:, :-1],
                      y_test=test[:, -1], adjacency=adj,
                      nbr_idx=padded_neighbors(adj))


def init(key, cfg, dtype=jnp.float32):
    """One node's weights: uniform(-1/sqrt(embed), 1/sqrt(embed))."""
    v, e = cfg["world"]["vocab"], cfg["model"]["embed"]
    bound = 1.0 / math.sqrt(e)
    ke, kw, kb = jax.random.split(key, 3)
    return {"emb": {"w": jax.random.uniform(ke, (v, e), jnp.float32, -bound,
                                            bound).astype(dtype)},
            "out": {"w": jax.random.uniform(kw, (e, v), jnp.float32, -bound,
                                            bound).astype(dtype),
                    "b": jax.random.uniform(kb, (v,), jnp.float32, -bound,
                                            bound).astype(dtype)}}


def apply(params, x, cfg):
    """Logits [B, V] of token ids [B, S]."""
    h = jnp.mean(params["emb"]["w"][x], axis=1)
    return jnp.dot(h, params["out"]["w"]) + params["out"]["b"]


def loss(params, x, y, cfg):
    """Mean KL(p_t || softmax(z)), p_t = beta on the label and the rest of
    the mass even: -H(p_t) - a * sum(log p) - (beta - a) * log p[label]."""
    logp = jax.nn.log_softmax(apply(params, x, cfg), axis=-1)
    v = logp.shape[-1]
    beta = cfg["method"]["beta"]
    a = (1.0 - beta) / (v - 1)
    neg_entropy = beta * math.log(beta) + (v - 1) * a * math.log(a)
    at_label = jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    return jnp.mean(neg_entropy - a * jnp.sum(logp, axis=-1)
                    - (beta - a) * at_label)


def score(params, x, y, cfg):
    """(CE summed, correct summed, labels counted) of one eval block."""
    z = apply(params, x, cfg)
    logp = jax.nn.log_softmax(z, axis=-1)
    ce = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    ok = jnp.argmax(z, axis=-1) == y
    return (jnp.sum(ce.astype(jnp.float32)), jnp.sum(ok.astype(jnp.float32)),
            y.size)


def flops_per_call(cfg, nodes: int, rounds: int, evals: int,
                   eval_samples: int) -> float:
    """Model FLOPs of one call: the dense layer's 2 x embed x vocab a
    sample, three times over for forward and backward."""
    meth = cfg["method"]
    macs = cfg["model"]["embed"] * cfg["world"]["vocab"]
    train = 6 * macs * meth["batch_size"] * meth["local_steps"] * nodes
    return float(train * rounds + 2 * macs * eval_samples * nodes * evals)


def program_model(cfg):
    """The program's model object: the same model as a SmallModel."""
    from repro.models.api import SmallModel

    def program_init(rng):
        return init(rng, cfg)

    def program_apply(params, x, *, train=False, rng=None):
        del train, rng
        h = jnp.take(params["emb"]["w"], x, axis=0).mean(axis=1)
        return h @ params["out"]["w"] + params["out"]["b"]

    return SmallModel("toy-tokens", program_init, program_apply,
                      cfg["world"]["vocab"])
