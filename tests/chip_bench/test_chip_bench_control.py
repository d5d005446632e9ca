"""The control of the check: the reference with fp8 operands in the
program's place fails the cell's limits. On the chip it is read at the
cells' own size; here at a size the CPU holds."""
import pytest

from benchmarks.chip import control


@pytest.mark.parametrize("cell,seed", [("mnv2.3stage.kill", 1),
                                       ("mnv2.4chip.steady", 2)])
def test_control_is_not_correct(cell, seed, tiny_cell):
    out = control.readings(tiny_cell(cell), seed)
    assert out["correct"] is False, out["checks"]
