"""The control fails the cell's limits (``feelbench/control.py``).

At the tests' tiny size on the CPU: the reference in bfloat16, put in
the program's place, must fail at least one of each cell's compared
numbers under the cell's own limits.  The chip readings at the cells'
own sizes are in ``PERF.md``.
"""

import pytest

from feelbench import control
from feelbench.tests import tiny


@pytest.mark.parametrize("name", ["cnn-das-s8", "cnn-full-s8"])
def test_control_fails_a_limit(name):
    cell = tiny.cell(name)
    got = control.control_numbers(cell, 77)["bf16"]
    limits = cell["limits"]
    assert {k for k, v in got.items() if v > limits[k]}, got
