"""The categorical training cell's yardstick at a size the CPU holds: the
generator draws what the configuration states and the same bits at 1
and at 8 threads; a sound run is correct against the plain reference
(bench/reference/gbdt_cat_ref.py); the control (float8 operands into the
histograms) and each planted fault (bench/faults_cat.py) come out not
correct by one of the limits; the map check reads a broken map; and the
kind refuses, before any data is made, a program that would route a
categorical table to the decision matrix or walk its held-out rows with
the gather walk.

On the CPU the program takes its portable XLA histograms (float32
operands), so the reference is told float32 and the limits are the toy's
own, tighter than the cell file's (set from the chip's readings at 100M
rows with bfloat16 operands: PERF.md section 2)."""

import hashlib

import numpy as np
import pytest

import compare
import datagen_cat
import faults_cat
import run as harness
from reference import gbdt_cat_ref

CELL = "expo-cat-b63.train-eval"
# 200,000 rows: every airport tier and the 99 % cut inside the non-hubs
# (about 242 bins, B = 248) as at full size; 31 leaves
TOY = {"cell": {"data": {"rows": 200000}, "heldout": {"rows": 50000},
                "limits": {"cat_map_mismatch": 0.0, "bin_edges_bad": 0.0,
                           "bin_mass_gap": 0.3, "bin_mismatch": 0.0,
                           "split_gain_gap": 1e-3, "leaf_value_gap": 1e-3,
                           "count_gap": 1e-4, "score_gap": 1e-3,
                           "loss_gap": 1e-5, "heldout_score_gap": 1e-3,
                           "auc_gap": 1e-5},
                "job": {"chunk_iters": 2, "threads": 4,
                        "check": {"hist_trees": 2, "sub": 2048,
                                  "upload_subs": 8}}},
       "config": {"params": {"num_leaves": 31},
                  "histogram_operand_dtype": "float32"}}
SEED = 2147483999


def _run(tamper=None, control=None):
    return harness.run_cell(CELL, SEED, 0.1, False, require_chip=False,
                            tamper=tamper, overrides=TOY,
                            control_dtype=control)


def test_sound_run_is_correct_and_the_control_is_not():
    loaded = harness.load_cell(CELL)
    res = _run(control=loaded["config"]["control_operand_dtype"])
    assert res["correct"], res["compared"]
    verdict = compare.judge(res["control"], TOY["cell"]["limits"])
    assert not verdict["correct"], verdict
    assert res["info"]["compiles_in_window"] == 0


@pytest.mark.parametrize("fault,number", [
    ("BitFlipped", "score_gap"), ("LeafOff", "leaf_value_gap"),
    ("StaleAuc", "auc_gap"), ("StateUnchanged", "score_gap"),
    ("HalfTheBatch", "count_gap"), ("CoarseBins", "bin_mass_gap")])
def test_fault_reads_not_correct(fault, number):
    res = _run(tamper=faults_cat.FAULTS[fault]())
    v, lim = res["compared"][number]
    assert not res["correct"] and v > lim, res["compared"]


@pytest.mark.parametrize("refusal", ["matrix_route", "gather_walk"])
def test_kind_refuses_a_program_off_the_kernels(refusal, monkeypatch):
    from lightgbm_tpu.ops import grow_wave, predict
    if refusal == "matrix_route":
        monkeypatch.setattr(grow_wave, "wave_route", lambda X, cfg: "matrix")
    else:
        monkeypatch.setattr(predict, "path_walk_applies",
                            lambda X, is_cat: is_cat is None)
    made = []
    monkeypatch.setattr(datagen_cat, "make",
                        lambda *a, **k: made.append(a) or None)
    with pytest.raises(SystemExit, match="train_cat"):
        _run()
    assert not made


def _map_follower(X, maps):
    edges = [np.array([np.inf])] * X.shape[1]
    for f in (3, 7):
        edges[f] = np.concatenate([np.quantile(X[:, f], np.linspace(
            0.02, 0.98, 40)), [np.inf]])
    fol = gbdt_cat_ref.CatFollower(edges, maps, {"num_leaves": 7},
                                   sub=2048, upload_subs=4)
    fol.load_rows(X, np.zeros(len(X), np.float32))
    return fol


def test_map_check_reads_a_broken_map():
    """The published rule on the generator's columns: descending counts
    into bins 1..k reads 0; a map that leaves out a large hub, or that
    maps one category to two bins' place, reads 1 a column."""
    X, _ = datagen_cat.make(SEED, {"kind": "airline", "rows": 100000})
    maps = {}
    for f in datagen_cat.CATEGORICAL:
        cnt = np.bincount(X[:, f].astype(np.int64))
        order = np.argsort(-cnt, kind="stable")
        keep = order[:max(1, int(np.searchsorted(
            np.cumsum(cnt[order]) / cnt.sum(), 0.99)) + 1)]
        maps[f] = {int(c): i + 1 for i, c in enumerate(keep)}
    assert _map_follower(X, maps).map_numbers(63)["cat_map_mismatch"] == 0
    top = min(maps[5], key=maps[5].get)               # bin 1: a large hub
    hub = {c: i + 1 for i, c in enumerate(
        sorted((c for c in maps[5] if c != top), key=maps[5].get))}
    gap = dict(maps[6])
    gap[max(gap, key=gap.get)] += 1                   # bins skip one
    broken = {**maps, 5: hub, 6: gap}
    assert _map_follower(X, broken).map_numbers(63)["cat_map_mismatch"] == 2


SPEC = {"kind": "airline", "rows": 2500000}


@pytest.fixture(scope="module")
def flights():
    return datagen_cat.make(SEED, SPEC, 0, 8)


def test_generator_is_the_same_at_1_and_8_threads(flights):
    X, y = flights
    X1, y1 = datagen_cat.make(SEED, SPEC, 0, 1)
    assert hashlib.md5(X.tobytes() + y.tobytes()).hexdigest() \
        == hashlib.md5(X1.tobytes() + y1.tobytes()).hexdigest()
    Xh, _ = datagen_cat.make(SEED, dict(SPEC, rows=100000), 1, 8)
    assert not np.array_equal(Xh, X[:100000])       # other rows


def test_generator_draws_the_stated_columns(flights):
    X, y = flights
    for f, n in datagen_cat.CARDINALITY.items():
        codes = X[:, f].astype(np.int64)
        assert codes.min() == 0 and codes.max() == n - 1
        assert len(np.unique(codes)) == n
    assert abs(float(y.mean()) - datagen_cat.POSITIVE_SHARE) < 0.01
    # the airport tiers' shares, and equal shares within a tier
    world = datagen_cat.World(SEED)
    share = np.bincount(X[:, 5].astype(np.int64), minlength=313) / len(X)
    lo = 0
    for n, s in datagen_cat.TIERS:
        tier = share[world.air_code[lo:lo + n]]
        assert abs(tier.sum() - s) < 0.003, (n, tier.sum())
        assert tier.std() < 0.2 * tier.mean() + 2e-5, (n, tier.std())
        lo += n
    carrier = np.bincount(X[:, 4].astype(np.int64))[world.carrier_code]
    assert np.all(np.diff(carrier[:5]) < 0)            # Zipf: falling
    hhmm = X[:, 3].astype(np.int64)
    assert hhmm.max() <= 2359 and np.all(hhmm % 100 < 60)
    assert X[:, 7].min() >= 30 and np.all(X[:, 7] == np.rint(X[:, 7]))
