"""The operation and byte counts the per-layer metrics divide by, against
hand counts from the configurations' shapes."""
import pytest

from bench.run import cell_spec
from bench.world import erdos_renyi, padded_neighbors

MLP_MACS = 784 * 512 + 512 * 256 + 256 * 128 + 128 * 10
CNN_MACS = (26 * 26 * 32 * 9 + 24 * 24 * 64 * 9 * 32 + 9216 * 128
            + 128 * 10)


@pytest.mark.parametrize("cell, macs, params", [
    ("mlp.gossip-fp32", 566_528, 567_434),
    ("cnn.gossip-fp32", 11_992_448, 1_199_882),
])
def test_macs_and_params_per_sample(cell, macs, params):
    spec = cell_spec(cell)
    assert spec["model"].macs_per_sample(spec["cfg"]) == macs
    assert spec["model"].param_count(spec["cfg"]) == params
    assert MLP_MACS == 566_528 and CNN_MACS == 11_992_448


def test_paper_graph_seed0_has_504_directed_edges():
    adj = erdos_renyi(50, 0.2, 0)
    assert int(adj.sum()) == 504
    assert (adj == adj.T).all() and not adj.diagonal().any()
    assert padded_neighbors(adj).shape == (50, 16)


@pytest.mark.parametrize("cell", ["mlp.gossip-fp32", "cnn.gossip-fp32"])
def test_flops_per_call(cell):
    spec = cell_spec(cell)
    cfg, model = spec["cfg"], spec["model"]
    macs = model.macs_per_sample(cfg)
    got = model.flops_per_call(cfg, nodes=50, rounds=100, evals=2,
                               eval_samples=9984)
    train = 3 * 2 * macs * 32 * 4 * 50 * 100
    evals = 2 * macs * 9984 * 50 * 2
    assert got == train + evals
    if cell.startswith("mlp"):
        assert train / 100 == pytest.approx(21.75e9, rel=1e-3)
    else:
        assert train / 100 == pytest.approx(460.5e9, rel=1e-3)


def test_segment_avg_least_time_is_bound_by_bytes():
    from bench.run import load_module, HERE
    import json
    import os

    roof = load_module(os.path.join(HERE, "metrics",
                                    "segment_avg.roofline.py"), "roof")
    peaks = json.load(open(os.path.join(HERE, "peaks.json")))["devices"]
    ctx = {"directed_edges": 504, "params_per_node": 567_434, "nodes": 50,
           "traffic": {"wire_bytes_per_value": 4},
           "peaks": peaks["TPU v5 lite"]}
    nbytes = 504 * 567_434 * 4 + 50 * 567_434 * 4
    assert roof.least_seconds(ctx) == pytest.approx(nbytes / 819e9)
    ctx["traffic"] = {"wire_bytes_per_value": 1}
    assert roof.least_seconds(ctx) == pytest.approx(
        (504 * 567_434 + 50 * 567_434 * 4) / 819e9)
