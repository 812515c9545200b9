"""The control has to come out not correct: the reference put in the
program's place with gradients and hessians entering the histogram sums
in the precision below the one the configuration states. Kept here at a
size a test run holds; read on the chip at the cell's own size with
bench/probe.py (PERF.md has the readings the limits were set from)."""

import pytest

import compare
import run as harness
from conftest import TOY


@pytest.mark.parametrize("cell", ["higgs28-b63.train",
                                  "higgs28-b255.train"])
def test_control_fails_a_limit(cell):
    loaded = harness.load_cell(cell)
    dtype = loaded["config"]["control_operand_dtype"]
    res = harness.run_cell(cell, 78, 0.1, False, require_chip=False,
                           overrides=TOY, control_dtype=dtype)
    limits = dict(loaded["cell"]["limits"], **TOY["cell"]["limits"])
    verdict = compare.judge(res["control"], limits)
    assert res["correct"], res["compared"]
    assert not verdict["correct"], verdict
