"""The device predictor's missing-value route at the Bosch deployment's
width (968 columns, four cells in five NaN in station blocks), on forests
loaded from model text, held bit for bit to the benchmark's own plain
reference (bench/reference/forest_ref_missing.py, imported by path as
bench/tests/conftest.py does: it imports nothing of the program and
walks the arrays the generator made, never the program's parse)."""

import os
import sys
import types

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models import predictor
from lightgbm_tpu.runtime import profiler

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import compare  # noqa: E402
import datagen_missing  # noqa: E402
import forestgen  # noqa: E402
from reference import forest_ref_missing  # noqa: E402

INTERP = "LIGHTGBM_TPU_PALLAS_INTERPRET"
F = 968
LIMITS = {"margin_gap": 1e-5, "rows_off_share": 1e-6,
          "rows_unanswered": 0.0, "passes_differ": 0.0}
BODIES = ["pallas_interpreter", "xla_scans"]


def _table(rows, seed=2**31 + 29, cols=F, stations=52):
    spec = {"kind": datagen_missing.KIND, "rows": rows, "cols": cols,
            "stations": stations, "missing_share": 0.81}
    X = datagen_missing.make(seed, spec, threads=2)
    assert abs(np.isnan(X).mean() - 0.81) < 0.03
    return X


def _forest(leaves, trees=6, all_types=False, seed=2**31 + 29):
    """forestgen's arrays at exactly ``leaves`` leaves a tree and the
    booster the program parses from their model text. ``all_types``: the
    nodes take the three missing types in turn (both default directions
    come from the seed)."""
    forest = forestgen.make(seed, {"trees": trees, "cols": F, "rows": 2600,
                                   "min_leaves": leaves,
                                   "max_leaves": leaves})
    if all_types:
        forest["missing_type"][:] = np.arange(leaves - 1)[None, :] % 3
        kinds = set(zip(forest["missing_type"].ravel().tolist(),
                        forest["default_left"].ravel().tolist()))
        assert len(kinds) == 6                  # 3 types x 2 directions
    booster = lgb.Booster(model_str=forestgen.model_text(forest, F))
    return forest, booster


def _device(monkeypatch, trees, X, body):
    """predict_margin_device as float32 [N], under the Pallas interpreter
    or as XLA scans, with the counts of its spans."""
    monkeypatch.setenv(INTERP, "1" if body == "pallas_interpreter" else "0")
    n0 = len(profiler.spans())
    with profiler.span("t/missing"):
        out = predictor.predict_margin_device(trees, 1, X)
    counts = {}
    for r in profiler.spans()[n0:]:
        counts.update(r["counts"])
    return out[0].astype(np.float32), counts


@pytest.mark.parametrize("leaves", [15, 57])
@pytest.mark.parametrize("body", BODIES)
def test_loaded_forest_is_bit_equal_to_the_reference(monkeypatch, body,
                                                     leaves):
    forest, booster = _forest(leaves)
    X = _table(2600)
    ref = forest_ref_missing.score(X, forest, block=4096)
    got, counts = _device(monkeypatch, booster._gbdt.models, X, body)
    assert np.array_equal(got, ref)
    assert counts["fused"] == int(body == "pallas_interpreter")
    assert counts["has_nan"] == 1 and counts["f_pad"] == 992
    assert counts["m_pad"] == (32 if leaves == 15 else 64)
    # a few thresholds a column: one int8 digit a code, so the codes are
    # a byte a padded column, rows padded to the row tile
    assert counts["code_terms"] == 1 and counts["thresholds_max"] <= 126
    n = counts["row_tile"]
    assert counts["layout_bytes"] == -(-len(X) // n) * n * 992
    assert counts["table_bytes"] == predictor.build_device_tables(
        booster._gbdt.models, 1, F).nbytes


@pytest.mark.parametrize("body", BODIES)
def test_all_missing_types_and_default_directions(monkeypatch, body):
    forest, booster = _forest(40, all_types=True)
    X = _table(2600)
    rng = np.random.RandomState(3)
    X[rng.rand(*X.shape) < 0.02] = 0.0          # what Zero-type nodes test
    ref = forest_ref_missing.score(X, forest, block=4096)
    got, _ = _device(monkeypatch, booster._gbdt.models, X, body)
    assert np.array_equal(got, ref)
    # each type decides something: read every node as type NaN, or every
    # NaN as zero, and rows move
    as_nan = dict(forest, missing_type=np.full_like(forest["missing_type"],
                                                    forestgen.MISSING_NAN))
    assert not np.array_equal(
        forest_ref_missing.score(X, as_nan, block=4096), ref)
    assert not np.array_equal(
        forest_ref_missing.score(X, forest, block=4096, nan_as_zero=True),
        ref)


def test_planted_fault_nan_as_zero_disagrees(monkeypatch):
    forest, booster = _forest(57)
    X = _table(2600)
    ref = forest_ref_missing.score(X, forest, block=4096)
    fault = forest_ref_missing.score(X, forest, block=4096, nan_as_zero=True)
    assert not compare.judge(compare.score_numbers(ref, fault, 0),
                             LIMITS)["correct"]
    # and the predictor, handed the imputed table, gives the fault's bits
    got, _ = _device(monkeypatch, booster._gbdt.models,
                     np.nan_to_num(X, nan=0.0), "xla_scans")
    assert np.array_equal(got, fault) and not np.array_equal(got, ref)


def _trained(X, leaves, rounds, **extra):
    rng = np.random.RandomState(11)
    w = rng.normal(size=X.shape[1])
    score = np.nansum(X * w, axis=1) + rng.normal(size=len(X))
    y = (score > np.quantile(score, 0.8)).astype(np.float64)
    params = dict({"objective": "binary", "num_leaves": leaves,
                   "max_bin": 63, "min_data_in_leaf": 1,
                   "min_sum_hessian_in_leaf": 0.05, "verbose": -1}, **extra)
    return lgb.train(params, lgb.Dataset(X, label=y),
                     num_boost_round=rounds)


def test_forest_trained_on_holed_data_walks_as_the_reference(monkeypatch):
    """lgb.train on the holed table, the dump brought into the
    reference's arrays, then the public entry by the cell's own limits
    and the device predictor bit for bit (200 columns: the CPU's grower
    compiles a select chain a column)."""
    X = _table(6000, cols=200)
    bst = _trained(X, 15, 3)
    forest = forest_ref_missing.from_dump(bst.dump_model()["tree_info"])
    facts = forest_ref_missing.forest_facts(forest)
    assert facts["forest_nan_nodes"] > 0 and facts["forest_max_leaves"] > 2
    ref = forest_ref_missing.score(X, forest, block=2048)
    nums = compare.score_numbers(ref, bst.predict(X, raw_score=True), 0)
    assert compare.judge(nums, LIMITS)["correct"], nums
    got, counts = _device(monkeypatch, bst._gbdt.models, X, "xla_scans")
    assert np.array_equal(got, ref) and counts["has_nan"] == 1


@pytest.mark.parametrize("body", BODIES)
def test_row_blocks_of_the_holed_table_are_bit_equal(monkeypatch,
                                                     force_row_blocks, body):
    """The has_nan route over three row blocks (the block rule patched to
    one row tile a block; the last block overlaps the second): the bits
    of the one-block call and of the reference."""
    forest, booster = _forest(15, trees=4)
    X = _table(9000)
    trees = booster._gbdt.models
    one, counts = _device(monkeypatch, trees, X, body)
    assert (counts["blocks"], counts["has_nan"]) == (1, 1)
    force_row_blocks()
    many, counts = _device(monkeypatch, trees, X, body)
    assert counts["blocks"] == 3 and counts["block_rows"] == 4096
    assert np.array_equal(one, many)
    assert np.array_equal(many, forest_ref_missing.score(X, forest,
                                                        block=4096))


def test_forest_over_the_table_budget_is_counted(monkeypatch):
    """A forest whose tables pass the device's budget takes the host
    walk, and the entry says so: tables_over_budget on predict/raw."""
    import jax
    _, booster = _forest(15, trees=3)
    X = _table(2000)
    big = np.concatenate([X] * 50)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(predictor, "device_tables_budget",
                        lambda rows, cols, layout_row_bytes: 1000)
    got = booster.predict(big, raw_score=True)
    raw = [r for r in profiler.spans() if r["name"] == "predict/raw"][-1]
    assert raw["counts"]["tables_over_budget"] == 1
    assert raw["counts"]["device_route"] == 0
    monkeypatch.undo()
    assert np.array_equal(got[:2000], booster.predict(X, raw_score=True))
    raw = [r for r in profiler.spans() if r["name"] == "predict/raw"][-1]
    assert "tables_over_budget" not in raw["counts"]


def test_device_route_takes_float32_rows_as_they_are(monkeypatch):
    """On the device route no host copy of the table is made: the
    predictor is handed the caller's float32 array itself, and the call
    opens no cast span. Float64 rows, and float32 rows the device cannot
    take, are cast once for the host walk."""
    import jax
    _, booster = _forest(15, trees=3)
    X = np.concatenate([_table(2000)] * 50)
    seen = []

    def fake(trees, K, rows, tables=None):
        seen.append(rows)
        return np.zeros((K, len(rows)))

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(predictor, "predict_margin_device", fake)
    fits = types.SimpleNamespace(over_budget=lambda rows: False)
    monkeypatch.setattr(predictor, "build_device_tables",
                        lambda trees, K, F, rows: fits)
    n0 = len(profiler.spans())
    booster.predict(X, raw_score=True)
    names = [r["name"] for r in profiler.spans()[n0:]]
    assert seen[0] is X
    assert not [n for n in names if n.startswith("predict/cast_")]
    want = booster.predict(X[:3000], raw_score=True)    # under 100,000 rows
    n0 = len(profiler.spans())
    got = booster.predict(X.astype(np.float64), raw_score=True)
    names = [r["name"] for r in profiler.spans()[n0:]]
    assert len(seen) == 1 and np.array_equal(got[:3000], want)
    assert names[:3] == ["predict/to_numpy", "predict/cast_f64",
                         "predict/host_walk"]


@pytest.mark.parametrize("rows,cols,layout_row_bytes,memory,budget", [
    # a v5e's 16.9 GB beside the Bosch table and the Higgs table: X, its
    # transposed copy and a byte of code a padded cell
    (1_000_000, 968, 992 * 5, 16_909_336_576,
     16_909_336_576 - 8_832_000_000),
    (10_500_000, 28, 32 * 5, 16_909_336_576, 16_909_336_576 - 2_856_000_000),
    # two digits a code (a 255-bin forest): the ranks are held as int32
    (10_500_000, 28, 32 * 14, 16_909_336_576,
     16_909_336_576 - 5_880_000_000),
    # rows that do not fit alone leave less than nothing
    (2_000_000, 968, 992 * 5, 16_909_336_576,
     16_909_336_576 - 17_664_000_000),
    # a backend that states no memory: the old line
    (1_000_000, 968, 992 * 5, None, 300_000_000),
])
def test_table_budget_is_what_the_rows_leave(monkeypatch, rows, cols,
                                             layout_row_bytes, memory,
                                             budget):
    monkeypatch.setattr(predictor, "_device_memory_bytes", lambda: memory)
    assert predictor.device_tables_budget(rows, cols,
                                          layout_row_bytes) == budget


def test_the_source_settings_at_the_leaf_cap_pass_a_v5e_budget(monkeypatch):
    """500 trees at the 255-leaf cap over 968 columns: 163 MB of tables
    with the int8 selector (782 MB as three bfloat16 terms a column),
    under what a v5e has beside the rows; a device with no room for them
    beside the blocks in flight gets no upload."""
    forest, booster = _forest(255, trees=1)
    tables = predictor.build_device_tables(booster._gbdt.models, 1, F)
    one = tables.nbytes - tables.tkeys.nbytes           # the per-tree part
    assert one == 256 * (992 + 256 + 5 * 4) + 256 * 4
    assert 150_000_000 < 500 * one < 170_000_000
    monkeypatch.setattr(predictor, "_device_memory_bytes",
                        lambda: 16_909_336_576)
    assert not tables.over_budget(1_000_000)
    assert tables.layout_row_bytes == 992 * 5
    assert 500 * one < predictor.device_tables_budget(1_000_000, F, 992 * 5)
    # 2M rows would not fit at once; scored in blocks they never are
    # there at once: 3 blocks of 256 MiB at most, whatever the table
    assert predictor.device_tables_budget(2_000_000, F, 992 * 5) < 0
    assert 100_000 < tables.resident_rows(2_000_000) \
        <= 3 * (256 << 20) // (4 * F)
    assert not tables.over_budget(2_000_000)
    monkeypatch.setattr(predictor, "_device_memory_bytes",
                        lambda: 1_000_000_000)
    assert tables.over_budget(1_000_000)
    assert predictor.build_device_tables(booster._gbdt.models, 1, F,
                                         rows=1_000_000) is None


def test_load_span_and_new_counts_reach_get_profile(monkeypatch):
    X = _table(1500, cols=40, stations=8)
    trained = _trained(X, 7, 2, device_profile=True)
    forest, booster = _forest(15, trees=4)
    n0 = len(profiler.spans())
    again = lgb.Booster(model_str=forestgen.model_text(forest, F))
    _device(monkeypatch, again._gbdt.models, _table(600), "xla_scans")
    recs = trained.get_profile()["spans"][n0:]
    by_name = {r["name"]: r for r in recs}
    load = by_name["booster/load"]
    assert load["parent"] is None
    assert load["counts"] == {"trees": 4, "bytes": len(
        forestgen.model_text(forest, F))}
    assert {"has_nan", "f_pad", "m_pad", "table_bytes", "fused", "row_tile",
            "code_terms", "thresholds_max"} <= set(
                by_name["predict/dispatch"]["counts"])
    assert by_name["predict/layout"]["counts"]["layout_bytes"] > 0
    # what the tables are is said where they are built, too
    built, used = (by_name[n]["counts"] for n in ("predict/tables",
                                                  "predict/dispatch"))
    for k in ("f_pad", "m_pad", "table_bytes", "code_terms",
              "thresholds_max"):
        assert built[k] == used[k]
    # one span a load, none a tree
    assert sum(r["name"].startswith("booster/load") for r in recs) == 1


def test_reference_raises_on_a_categorical_node():
    node = {"split_index": 0, "split_feature": 0, "threshold": "1||2",
            "decision_type": "==", "default_left": False,
            "missing_type": "None",
            "left_child": {"leaf_index": 0, "leaf_value": 0.1},
            "right_child": {"leaf_index": 1, "leaf_value": -0.1}}
    forest = forest_ref_missing.from_dump([{"tree_structure": node}])
    with pytest.raises(ValueError):
        forest_ref_missing.score(np.zeros((4, 2), np.float32), forest)
