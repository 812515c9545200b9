"""The ranking cell's yardstick at a size the CPU holds: a sound run is
correct; the control (the reference one precision below: float8 operands
into the histograms, bfloat16 pairs) and each planted fault
(bench/faults_rank.py) come out not correct by one of the limits; the
generator gives the recorded bits at 1 and at 8 threads.

On the CPU the program takes its portable XLA histograms (float32
operands), so the reference is told float32 and the limits are the toy's
own, tighter than the cell file's (set from the chip's readings at 13.6M
rows with bfloat16 operands: PERF.md section 2)."""

import hashlib

import pytest

import compare
import datagen_rank
import faults_rank
import run as harness

CELL = "mslr137-b63.train-eval"
TOY = {"cell": {"data": {"rows": 40000, "queries": 400, "max_len": 400},
                "heldout": {"rows": 12000, "queries": 120},
                "limits": {"bin_edges_bad": 0.0, "bin_mass_gap": 0.3,
                           "bin_mismatch": 0.0, "split_gain_gap": 1e-3,
                           "leaf_value_gap": 1e-3, "score_gap": 1e-3,
                           "heldout_score_gap": 1e-3, "lambda_gap": 2e-4,
                           "ndcg_gap": 1e-5},
                "job": {"chunk_iters": 2,
                        "check": {"hist_trees": 2, "sub": 2048,
                                  "upload_subs": 4}}},
       "config": {"params": {"num_leaves": 15,
                             "min_sum_hessian_in_leaf": 1.0},
                  "histogram_operand_dtype": "float32"}}


def _run(tamper=None, control=None):
    return harness.run_cell(CELL, 2147483999, 0.1, False, require_chip=False,
                            tamper=tamper, overrides=TOY,
                            control_dtype=control)


def test_sound_run_is_correct_and_the_control_is_not():
    loaded = harness.load_cell(CELL)
    res = _run(control=loaded["config"]["control_operand_dtype"])
    assert res["correct"], res["compared"]
    verdict = compare.judge(res["control"], TOY["cell"]["limits"])
    assert not verdict["correct"], verdict
    # the job stayed in the scan: one dispatch a chunk of 2 trees
    assert res["info"]["compiles_in_window"] == 0


@pytest.mark.parametrize("fault,number", [
    ("StaleNdcg", "ndcg_gap"), ("TruncationOff", "lambda_gap"),
    ("GroupShift", "ndcg_gap")])
def test_fault_reads_not_correct(fault, number):
    res = _run(tamper=faults_rank.FAULTS[fault]())
    v, lim = res["compared"][number]
    assert not res["correct"] and v > lim, res["compared"]


SPEC = {"kind": "rank_streams", "rows": 70000, "queries": 600, "cols": 137}
RECORDED = {0: "96f0d53dc58805f8490978ab6441490a", 1: "d7a8756dfc3e2743842541027666f7c8"}


@pytest.mark.parametrize("threads", [1, 8])
@pytest.mark.parametrize("part", [0, 1])
def test_generator_reads_the_recorded_bits(part, threads):
    """More than two blocks of 2**15 rows, so the threads have blocks to
    share; the held-out part is other rows under the same weights."""
    X, y, ln = datagen_rank.make(2147483999, SPEC, part, threads)
    assert int(ln.sum()) == len(X) == SPEC["rows"] and ln.min() >= 1
    assert hashlib.md5(X.tobytes() + y.tobytes()
                       + ln.tobytes()).hexdigest() == RECORDED[part]


def test_every_seed_has_one_multiset_of_lengths():
    a = datagen_rank.make(1, SPEC)[2]
    b = datagen_rank.make(2, SPEC)[2]
    assert sorted(a) == sorted(b) and list(a) != list(b)
    assert a.max() <= 1251 and abs(a.mean() - SPEC["rows"] / 600) < 1e-9
