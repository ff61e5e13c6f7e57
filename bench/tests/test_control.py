"""The lower-precision control, put in the program's place, is not
correct: `bench/control.py` at a small size on the CPU (on the chip it
runs at the cells' own sizes; PERF.md gives those readings)."""
import pytest

from bench import control, correct
from bench.tests.test_faults import CELLS


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_bfloat16_control_fails(cell):
    spec = CELLS[cell]
    lines = control.readings(cell, [5, 6], program=False,
                             variants=("bf16",), emit=lambda s: None, **spec)
    lim = correct.limits(cell, spec.get("where", correct.HERE))
    for line in lines:
        ok, checks = correct.judge(line["gaps"], lim)
        assert not ok, checks
