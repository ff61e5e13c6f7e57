"""The reference's configuration hooks and their defaults.

The pinned readings are the reference's own on the shrunk MLP cell of
test_faults.py (seed 5), taken once at the commit before the hooks came
in: the paper's cells read the same numbers through the default hooks,
to the last digit, in both precisions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import drive, reference
from bench.run import cell_spec, make_world
from bench.tests.test_faults import CELLS, SMALL
from bench.world import PaperWorld

PINNED = {
    "float32": {
        "loss0": 2.303178310394287,
        "loss": [2.3026647567749023, 2.3024532794952393, 2.302406072616577],
        "acc": [0.096923828125, 0.092529296875, 0.095458984375],
        "mom1": {"['fc0']['b']": 0.038134124130010605,
                 "['fc0']['w']": 0.7377091646194458,
                 "['fc1']['b']": 0.1329655945301056,
                 "['fc1']['w']": 0.7862009406089783,
                 "['fc2']['b']": 0.4368014335632324,
                 "['fc2']['w']": 0.7153624296188354,
                 "['fc3']['b']": 1.390478253364563,
                 "['fc3']['w']": 0.6800559163093567},
        "dparam": {"['fc0']['b']": 0.8434802889823914,
                   "['fc0']['w']": 23.38201904296875,
                   "['fc1']['b']": 0.7111638784408569,
                   "['fc1']['w']": 16.53016471862793,
                   "['fc2']['b']": 0.729006826877594,
                   "['fc2']['w']": 11.673063278198242,
                   "['fc3']['b']": 0.33227017521858215,
                   "['fc3']['w']": 3.2660019397735596},
    },
    "bfloat16": {
        "loss0": 2.3038978576660156,
        "loss": [2.303802490234375, 2.3036956787109375, 2.3031959533691406],
        "acc": [0.099853515625, 0.093994140625, 0.095458984375],
        "mom1": {"['fc0']['b']": 0.03805433213710785,
                 "['fc0']['w']": 0.7364404201507568,
                 "['fc1']['b']": 0.13221989572048187,
                 "['fc1']['w']": 0.7832063436508179,
                 "['fc2']['b']": 0.43544644117355347,
                 "['fc2']['w']": 0.7125790119171143,
                 "['fc3']['b']": 1.3998223543167114,
                 "['fc3']['w']": 0.6782230734825134},
        "dparam": {"['fc0']['b']": 0.8437607884407043,
                   "['fc0']['w']": 23.387298583984375,
                   "['fc1']['b']": 0.7114806771278381,
                   "['fc1']['w']": 16.534564971923828,
                   "['fc2']['b']": 0.7298845648765564,
                   "['fc2']['w']": 11.675928115844727,
                   "['fc3']['b']": 0.3334301710128784,
                   "['fc3']['w']": 3.266793727874756},
    },
}


@pytest.mark.parametrize("dtype", sorted(PINNED))
def test_default_hooks_read_as_before(dtype):
    spec = cell_spec("mlp.gossip-fp32", SMALL)
    cfg, model, traffic = spec["cfg"], spec["model"], spec["traffic"]
    world = make_world(spec)
    got = reference.reference_run(
        model, cfg, world, drive.make_params(model, cfg, 5, world.num_nodes),
        traffic["rounds_per_call"], traffic["set_up_calls"],
        dtype=getattr(jnp, dtype))
    assert got == PINNED[dtype]


def test_bfloat16_keeps_token_ids():
    spec = cell_spec("toy.gossip", **CELLS["toy.gossip"])
    world = make_world(spec)
    x_pad, y_pad, _, _, x_test, _ = reference.reference_data(
        spec["cfg"], world, jnp.bfloat16)
    assert x_pad.dtype == x_test.dtype == jnp.int32
    assert int(x_pad.max()) > 256 and int(y_pad.max()) > 256
    for i, (x, y) in enumerate(zip(world.xs, world.ys)):
        np.testing.assert_array_equal(np.asarray(x_pad[i, :len(x)]), x)
        np.testing.assert_array_equal(np.asarray(y_pad[i, :len(y)]), y)
    np.testing.assert_array_equal(np.asarray(x_test),
                                  world.x_test[:x_test.shape[0]])


def _ce(z, y):
    return (jax.nn.logsumexp(z, axis=-1)
            - jnp.take_along_axis(z, y[..., None], axis=-1)[..., 0])


class PerPosition:
    """A model that predicts every position's label ([B, S] labels)."""

    @staticmethod
    def apply(params, x, cfg):
        return params["w"][x]

    @staticmethod
    def loss(params, x, y, cfg):
        return jnp.mean(_ce(PerPosition.apply(params, x, cfg), y))

    @staticmethod
    def score(params, x, y, cfg):
        z = PerPosition.apply(params, x, cfg)
        hits = (jnp.argmax(z, axis=-1) == y).astype(jnp.float32)
        return jnp.sum(_ce(z, y)), jnp.sum(hits), y.size


@pytest.mark.parametrize("eval_block", [3, 4])
def test_score_counts_every_position(eval_block):
    """Accuracy and loss are over B x S labels; in blocks (4 tiles the 8
    test rows) and whole (3 does not)."""
    rng = np.random.default_rng(1)
    vocab, seq = 5, 6
    tokens = rng.integers(0, vocab, size=(3, 10, seq)).astype(np.int32)
    world = PaperWorld(
        xs=[tokens[0], tokens[1]], ys=[tokens[0], tokens[1]],
        x_test=tokens[2, :8], y_test=(tokens[2, :8] + 1) % vocab,
        adjacency=np.array([[0, 1], [1, 0]], np.int8),
        nbr_idx=np.array([[1], [0]], np.int32))
    cfg = {"vocab": vocab, "reference": {"eval_block": eval_block},
           "method": {"batch_size": 4, "local_steps": 1, "lr": 0.1,
                      "momentum": 0.0, "s": 1.0, "eval_batch": 8}}
    params = {"w": jnp.asarray(rng.normal(size=(2, vocab, vocab)),
                               jnp.float32)}
    call = reference.make_reference(PerPosition, cfg, 1)
    out, _, (_, (ce, acc)) = call(
        params, jax.tree.map(jnp.zeros_like, params),
        reference.reference_data(cfg, world))
    z = np.asarray(out["w"])[:, world.x_test]          # [N, 8, S, V]
    hits = z.argmax(-1) == world.y_test
    np.testing.assert_allclose(np.asarray(acc), hits.mean(axis=(1, 2)))
    lse = np.log(np.exp(z).sum(-1))
    z_y = np.take_along_axis(z, world.y_test[None, ..., None], -1)[..., 0]
    np.testing.assert_allclose(np.asarray(ce), (lse - z_y).mean(axis=(1, 2)),
                               rtol=1e-5)
