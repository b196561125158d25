"""Each cell's control, put in the program's place at a tiny size,
comes out not correct, while the program passes the same comparison."""

import pytest

import cells
from benchmarks import control


@pytest.mark.parametrize("seed", [11, 2**31 + 3])
@pytest.mark.parametrize("workload", cells.WORKLOADS)
def test_control_fails_where_the_program_passes(workload, seed):
    cell = cells.tiny_cell(workload)
    limits = cell.mix["limits"]
    program, ctrl = control.readings(cell, seed, cells.devices(cell))
    assert set(program) == set(ctrl) == set(limits)
    assert all(v <= limits[k] for k, v in program.items()), program
    assert any(v > limits[k] for k, v in ctrl.items()), ctrl
