"""The lower-precision control, put in the program's place, is not
correct: `bench/control.py` at a small size on the CPU (on the chip it
runs at the cells' own sizes; PERF.md gives those readings)."""
import pytest

from bench import control, correct

SMALL = {"world": {"seed": 3, "nodes": 8, "train_size": 2000,
                   "test_size": 512, "er_p": 0.5},
         "traffic": {"rounds_per_call": 4}}


@pytest.mark.parametrize("cell", ["mlp.gossip-fp32"])
def test_bfloat16_control_fails(cell):
    lines = control.readings(cell, [5, 6], program=False,
                             variants=("bf16",), overrides=SMALL,
                             emit=lambda s: None)
    lim = correct.limits(cell)
    for line in lines:
        ok, checks = correct.judge(line["gaps"], lim)
        assert not ok, checks
