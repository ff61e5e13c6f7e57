"""The trace reduction, on hand-made intervals and on a small trace
recorded on a TPU v5e (data/tiny.xplane.pb: two calls of a jitted
`segment_avg` reduce inside `bench.window`, each followed by 50 ms of
host work in a `bench.host_work` span)."""
import os

import pytest

from bench import trace as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "data")


def test_union_merges_overlaps_and_clips():
    iv = [(0, 10), (5, 15), (20, 30), (29, 31), (40, 50)]
    assert tr.union_ns(iv, 0, 100) == 15 + 11 + 10
    assert tr.union_ns(iv, 8, 45) == 7 + 11 + 5
    assert tr.union_ns([], 0, 10) == 0


def test_gaps_are_the_complement_of_the_union():
    us = 1000
    iv = [(10 * us, 20 * us), (15 * us, 25 * us), (40 * us, 50 * us),
          (50 * us + 10, 55 * us)]
    assert tr._gaps(iv, 0, 60 * us) == [(0, 10 * us), (25 * us, 40 * us),
                                        (55 * us, 60 * us)]
    assert tr._gaps(iv, 12 * us, 45 * us) == [(25 * us, 40 * us)]


def test_self_time_leaves_out_nested_ops():
    ops = [("%while.3 = (s32[]) while(...)", 0, 100),
           ("%fusion.1 = f32[8] fusion(...)", 10, 30),
           ("%segment_avg_chunk.5 = f32[8] fusion(...)", 40, 90),
           ("%fusion.1 = f32[8] fusion(...)", 95, 100)]
    got = tr.self_times(ops, 0, 100)
    assert got == {"while.3": 25, "fusion.1": 25, "segment_avg_chunk.5": 50}
    assert tr.self_times(ops, 50, 100) == {
        "while.3": 5, "segment_avg_chunk.5": 40, "fusion.1": 5}


def test_reduce_on_a_hand_made_trace():
    ms = 1e6
    trace = {
        "devices": {"/device:TPU:0": [("fusion.1", 0, 4 * ms),
                                       ("segment_avg_chunk.2", 4 * ms,
                                        7 * ms),
                                       ("fusion.1", 9 * ms, 10 * ms)],
                    "/device:TPU:1": []},
        "spans": [("bench.window", 0, 10 * ms)],
        "host": [("bench.window", 0, 10 * ms),
                 ("bench.fetch", 6.5 * ms, 9.5 * ms)],
    }
    r = tr.reduce(trace)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.008)
    assert tr.kernel_seconds(r, "segment_avg") == pytest.approx(0.003)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.005)]
    assert r["idle_gaps"] == [["bench.fetch", pytest.approx(0.002)]]


def test_reduce_on_a_recorded_tpu_trace():
    r = tr.reduce(tr.load(FIXTURE))
    assert r["devices"] == 1
    assert 0.1 < r["window_s"] < 5.0
    assert 0 < r["busy_s"] < r["window_s"]
    assert tr.kernel_seconds(r, "segment_avg") > 0
    # the longest idle gaps are the two 50 ms stretches of host work
    names = [g[0] for g in r["idle_gaps"][:2]]
    assert names == ["bench.host_work", "bench.host_work"]
    assert all(g[1] == pytest.approx(0.05, rel=0.2)
               for g in r["idle_gaps"][:2])
