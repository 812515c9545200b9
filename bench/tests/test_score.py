"""The score cell's control and planted faults, at a size the CPU holds.
The harness's look for a chip is skipped; on the CPU the public predict
entry walks on the host, so this drives the harness, the reference and
the comparison, and the chip run drives the device predictor."""

import numpy as np
import pytest

import compare
import run as harness

CELL = "higgs28-b63.score"
TOY = {"cell": {"data": {"rows": 200000},
                "job": {"train_rows": 30000, "train_chunk": 5,
                        "check": {"block": 65536}}},
       "config": {"params": {"num_leaves": 31, "num_iterations": 10,
                             "min_sum_hessian_in_leaf": 5.0}}}


class AnswerAltered:
    """One margin altered where it is produced."""

    def margins(self, m):
        m = np.array(m, copy=True)
        m[len(m) // 3] += 0.01
        return m


class HalfLeftOut:
    """Half of the table never scored: its margins come back as zeros."""

    def margins(self, m):
        m = np.array(m, copy=True)
        m[len(m) // 2:] = 0.0
        return m


class Unanswered:
    """A block of rows with no answer."""

    def margins(self, m):
        m = np.array(m, copy=True)
        m[:1000] = np.nan
        return m


def _run(tamper=None, control=None):
    return harness.run_cell(CELL, 79, 0.1, False, require_chip=False,
                            tamper=tamper, overrides=TOY,
                            control_dtype=control)


def test_sound_run_is_correct_and_control_is_not():
    loaded = harness.load_cell(CELL)
    res = _run(control=str(loaded["config"]["control_feature_terms"]))
    assert res["correct"], res["compared"]
    verdict = compare.judge(res["control"], loaded["cell"]["limits"])
    assert not verdict["correct"], verdict


@pytest.mark.parametrize("fault", [AnswerAltered, HalfLeftOut, Unanswered])
def test_fault_reads_not_correct(fault):
    res = _run(tamper=fault())
    over = [k for k, (v, lim) in res["compared"].items() if v > lim]
    assert not res["correct"] and over, res["compared"]
