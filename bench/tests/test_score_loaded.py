"""The loaded-forest score cell at a size the CPU holds: the two
generators, the model text, the control and the planted faults. The
harness's look for a chip is skipped; on the CPU the public predict entry
walks on the host, so this drives the harness, the generators, the
program's parse of the model text, the reference that follows
``missing_type`` and ``default_left``, and the comparison; the chip run
drives the device predictor (tests/test_predictor_missing.py holds that
to the same reference on the CPU, bit for bit)."""

import numpy as np
import pytest

import compare
import datagen_missing
import forestgen
import run as harness
from test_score import AnswerAltered, HalfLeftOut, Unanswered

CELL = "bosch968-b63.score"
# rows enough that the control's rounding moves some row across a
# threshold: four cells in five take no part in a compare
TOY = {"cell": {"data": {"rows": 200000, "cols": 120, "stations": 12},
                "job": {"check": {"block": 65536}}},
       "config": {"forest": {"trees": 40, "min_leaves": 9,
                             "max_leaves": 31}}}
SPEC = {"trees": 30, "cols": 968, "min_leaves": 33, "max_leaves": 57,
        "rows": 1000000}


class NanAsZero:
    """The program is handed the table with every NaN imputed as 0.0:
    its margins are those of a predictor that ignores the nodes' missing
    type and default direction."""

    def table(self, X):
        return np.nan_to_num(X, nan=0.0)


def _run(tamper=None, control=None):
    return harness.run_cell(CELL, 2**31 + 79, 0.1, False, require_chip=False,
                            tamper=tamper, overrides=TOY,
                            control_dtype=control)


def test_sound_run_is_correct_control_and_planted_fault_are_not():
    loaded = harness.load_cell(CELL)
    limits = loaded["cell"]["limits"]
    res = _run(control=str(loaded["config"]["control_feature_terms"]))
    assert res["correct"], res["compared"]
    control = dict(res["control"])
    fault = control.pop("nan_as_zero")
    assert not compare.judge(control, limits)["correct"], control
    assert not compare.judge(fault, limits)["correct"], fault


@pytest.mark.parametrize("fault", [AnswerAltered, HalfLeftOut, Unanswered,
                                   NanAsZero])
def test_fault_reads_not_correct(fault):
    res = _run(tamper=fault())
    over = [k for k, (v, lim) in res["compared"].items() if v > lim]
    assert not res["correct"] and over, res["compared"]


def test_generator_same_bits_any_threads_and_nan_share_in_band():
    spec = dict(harness.load_cell(CELL)["cell"]["data"], rows=150000)
    X8 = datagen_missing.make(2**31 + 5, spec, threads=8)
    X1 = datagen_missing.make(2**31 + 5, spec, threads=1)
    assert X8.dtype == np.float32 and X8.shape == (150000, 968)
    assert np.array_equal(X8.view(np.uint32), X1.view(np.uint32))
    nan = np.isnan(X8)
    assert abs(nan.mean() - spec["missing_share"]) < 0.01
    assert not nan.all(axis=0).any()          # no column is all NaN
    # a station's cells are present or absent together
    soc = datagen_missing.layout(2**31 + 5, spec)["station_of_col"]
    first = np.searchsorted(soc, np.arange(spec["stations"]))
    assert np.array_equal(nan, nan[:, first[soc]])
    other = datagen_missing.make(2**31 + 6, spec)
    assert not np.array_equal(np.isnan(other), nan)


def test_forestgen_repeats_its_seed_and_keeps_the_stated_shape():
    a, b = forestgen.make(2**31 + 5, SPEC), forestgen.make(2**31 + 5, SPEC)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert forestgen.model_text(a, 968) == forestgen.model_text(b, 968)
    other = forestgen.make(2**31 + 6, SPEC)
    assert not np.array_equal(a["threshold"], other["threshold"])
    n = a["num_leaves"]
    assert n.min() >= 33 and n.max() <= 57 and len(set(n.tolist())) > 5
    for t in range(len(n)):
        m = n[t] - 1
        kids = np.concatenate([a["left_child"][t, :m],
                               a["right_child"][t, :m]])
        # every node but the root and every leaf is some node's child, once
        assert sorted(kids[kids >= 0]) == list(range(1, m))
        assert sorted(~kids[kids < 0]) == list(range(n[t]))
        assert a["leaf_count"][t, :n[t]].sum() == SPEC["rows"]
        assert (a["missing_type"][t, :m] == forestgen.MISSING_NAN).all()
    assert 0.3 < a["default_left"][:, :32].mean() < 0.7
    assert a["split_feature"].max() < 968 and not a["categorical"].any()


def test_model_text_round_trips_through_the_programs_parser():
    import lightgbm_tpu as lgb
    from lightgbm_tpu.models.tree import MISSING_NAN
    forest = forestgen.make(2**31 + 9, SPEC)
    booster = lgb.Booster(model_str=forestgen.model_text(forest, 968))
    trees = booster._gbdt.models
    assert len(trees) == SPEC["trees"] and booster.num_feature() == 968
    for t, tree in enumerate(trees):
        n = int(forest["num_leaves"][t])
        m = n - 1
        assert tree.num_leaves == n and tree.num_cat == 0
        for key in ("split_feature", "threshold", "left_child",
                    "right_child"):
            assert np.array_equal(getattr(tree, key), forest[key][t, :m])
        for key in ("leaf_value", "leaf_count"):
            assert np.array_equal(getattr(tree, key), forest[key][t, :n])
        dt = tree.decision_type.astype(np.int64)
        assert ((dt >> 2 & 3) == MISSING_NAN).all() and not (dt & 1).any()
        assert np.array_equal((dt >> 1 & 1) == 1,
                              forest["default_left"][t, :m])
    # and a second trip through the program's own writer keeps the bits
    again = lgb.Booster(model_str=booster.model_to_string())
    assert all(np.array_equal(x.threshold, y.threshold)
               for x, y in zip(again._gbdt.models, trees))
