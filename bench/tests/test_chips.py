"""A cell on several chips, on 8 virtual CPU devices in a child process:
the reference with the node axis over 4 of them reads what it reads on
one, `dparam` reads the same with the starting point on the host as on
the device, the weights land in the program's mesh placement, and the
program under the shard_map backend is `correct` against that
reference."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TESTS = os.path.join(ROOT, "bench", "tests")
CELL = "toy.pods4"
SEED = 2 ** 33 + 11
# With the node axis over 4 devices the reference takes the neighbour
# average leaf by leaf and the partitioned sums (the average, the vmapped
# groups' reductions) run in another order than on one device, so float32
# readings differ in their last bits: up to 3.7e-7 relative (the first
# call's momentum of the embedding) on the CPU.
# 1e-6 is half of the toy's tightest limit (2e-6, bench/tests/limits), so
# laying the reference over the chips moves no verdict by more than that.
REL = 1e-6


def child():
    """Runs in the child; prints one JSON line."""
    import jax

    from bench import drive
    from bench.reference import (leaf_norms, make_reference, node_sharding,
                                 reference_data, reference_run)
    from bench.run import cell_spec, make_world, run

    spec = cell_spec(CELL, where=TESTS)
    cfg, model, traffic = spec["cfg"], spec["model"], spec["traffic"]
    world = make_world(spec)
    n, chips = world.num_nodes, spec["cell"]["chips"]
    rounds, calls = traffic["rounds_per_call"], traffic["set_up_calls"]
    readings = {c: reference_run(
        model, cfg, world,
        drive.make_params(model, cfg, SEED, n, sharding=node_sharding(c)),
        rounds, calls, chips=c) for c in (1, chips)}
    # dparam with the starting point kept on the device, beside the host
    # copy that reference_run and set_up_calls keep on several chips
    params = drive.make_params(model, cfg, SEED, n,
                               sharding=node_sharding(chips))
    theta0 = drive._copy(params)
    mom = jax.tree.map(jax.numpy.zeros_like, params)
    call = make_reference(model, cfg, rounds, chips=chips)
    data = reference_data(cfg, world, chips=chips)
    for _ in range(calls):
        params, mom, _ = call(params, mom, data)
    on_device = {"reference": leaf_norms(drive._diff(params, theta0))}
    place = drive.placement(traffic, chips)
    exp = drive.build_experiment(
        model, cfg, traffic, world,
        drive.make_params(model, cfg, SEED, n, sharding=place), SEED, place)
    theta0 = drive._copy(exp.params)
    on_host = {"reference": readings[chips]["dparam"],
               "program": drive.set_up_calls(exp, rounds, calls)["dparam"]}
    on_device["program"] = leaf_norms(drive._diff(exp.params, theta0))
    del exp
    params = drive.make_params(model, cfg, SEED, n, sharding=place)
    plain = drive.make_params(model, cfg, SEED, n)
    leaves = jax.tree.leaves(params)
    out = {
        "readings": readings,
        "dparam": {"on_host": on_host, "on_device": on_device},
        "weights_spec": [str(a.sharding.spec) for a in leaves],
        "weights_devices": sorted({d.id for a in leaves
                                   for d in a.sharding.device_set}),
        "weights_equal": all(bool((a == b).all()) for a, b in zip(
            leaves, jax.tree.leaves(plain))),
        "data_devices": [len(a.sharding.device_set) for a in data],
        "data_spec": [str(a.sharding.spec) for a in data],
        "run": run(CELL, SEED, 0.5, False, require_chip=False, where=TESTS),
    }
    print("RESULT " + json.dumps(out))


@pytest.fixture(scope="module")
def pods():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    code = ("import sys; sys.path[:0] = [%r, %r]; "
            "from bench.tests import test_chips; test_chips.child()"
            % (ROOT, os.path.join(ROOT, "src")))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_four_chips_read_as_one(pods):
    one, four = pods["readings"]["1"], pods["readings"]["4"]
    for key in ("loss0", "loss", "acc", "mom1", "dparam"):
        a, b = one[key], four[key]
        if isinstance(a, dict):
            a, b = [a[k] for k in sorted(a)], [b[k] for k in sorted(b)]
        elif not isinstance(a, list):
            a, b = [a], [b]
        for x, y in zip(a, b):
            assert abs(x - y) <= REL * abs(x), (key, one, four)


@pytest.mark.parametrize("side", ["reference", "program"])
def test_theta0_on_the_host_reads_as_on_the_device(pods, side):
    got = pods["dparam"]
    assert got["on_host"][side] == got["on_device"][side]


def test_weights_land_in_the_program_mesh(pods):
    assert pods["weights_devices"] == [0, 1, 2, 3]
    assert set(pods["weights_spec"]) == {"PartitionSpec('pod',)"}
    assert pods["weights_equal"]


def test_reference_lays_node_axis_over_the_chips(pods):
    assert pods["data_devices"] == [4] * 6
    assert pods["data_spec"][:3] == ["PartitionSpec('nodes',)"] * 3
    assert pods["data_spec"][3:] == ["PartitionSpec()"] * 3


def test_shard_map_cell_is_correct(pods):
    res = pods["run"]
    assert res["correct"], res["checks"]
