"""The lower-precision control comes out not correct, while the program
comes out correct, at a size a test run can hold: the check's limits
separate float32 serving from the same reference computed in bfloat16."""
import pytest

from fabric_bench import control
from fabric_bench.tests import tiny

pytest.importorskip("repro.core.simulator")


def _passes(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= v for k, v in limits.items())


@pytest.mark.parametrize("name", sorted(tiny.SIZES))
def test_control_fails_where_the_program_passes(name):
    cell = tiny.cell(name)
    r = control.readings(cell, 2**31 + 17)
    assert _passes(r["program"], cell.limits), r
    assert not _passes(r["control"], cell.limits), r
