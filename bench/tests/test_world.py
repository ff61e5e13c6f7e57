"""The benchmark's copy of the generators gives the program's arrays."""
import numpy as np
import pytest

from bench.world import build_world


@pytest.mark.parametrize("dataset, seed", [
    ("synth-mnist", 0), ("synth-mnist", 1), ("synth-mnist", 2),
    ("synth-fashion", 0)])
def test_copy_matches_world_synthetic(dataset, seed):
    from repro.engine import World

    ref = World.synthetic(dataset, nodes=50, topology="erdos_renyi", p=0.2,
                          scale=1.0, seed=seed)
    got = build_world({"seed": seed, "dataset": dataset,
                       "train_size": 60_000, "test_size": 10_000,
                       "nodes": 50, "er_p": 0.2, "zipf_alpha": 1.26,
                       "min_per_class": 1})
    assert len(got.xs) == len(ref.xs) == 50
    for a, b in zip(got.xs, ref.xs):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(got.ys, ref.ys):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(got.x_test, ref.x_test)
    np.testing.assert_array_equal(got.y_test, ref.y_test)
    np.testing.assert_array_equal(got.adjacency, ref.topo.adjacency)
    np.testing.assert_array_equal(got.nbr_idx, ref.topo.neighbor_idx)


def test_large_seed_is_accepted():
    w = build_world({"seed": 2 ** 33 + 7, "dataset": "synth-mnist",
                     "train_size": 800, "test_size": 200, "nodes": 8,
                     "er_p": 0.5, "zipf_alpha": 1.26, "min_per_class": 1})
    assert sum(len(x) for x in w.xs) == 800
