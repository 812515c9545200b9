"""The span-read metrics of higgs28-b63.score-fullfit, with the cell
shrunk to what a CPU holds. On the CPU the public predict entry walks on
the host and the trace has no device plane, so the forest_* metrics and
the entry's split are read and the two idle shares read nothing; the
chip run reads all twelve."""

import run as harness

CELL = "higgs28-b63.score-fullfit"
TOY = {"cell": {"data": {"rows": 120000},
                "job": {"train_rows": 30000, "train_chunk": 3,
                        "check": {"block": 65536}}},
       "config": {"params": {"num_leaves": 31, "num_iterations": 10,
                             "min_sum_hessian_in_leaf": 5.0}}}
NEW = ["score_cast_ms_per_pass", "score_transfer_ms_per_pass",
       "score_wait_device_ms_per_pass", "score_entry_other_ms_per_pass",
       "score_idle_named_pct", "score_idle_in_casts_pct",
       "forest_construct_s", "forest_chunks_s", "forest_drain_s",
       "forest_compile_s", "forest_init_s", "forest_stop_wait_s"]


def test_cell_names_the_accepted_five_and_the_new_twelve():
    loaded = harness.load_cell(CELL)
    accepted = harness.load_cell("higgs28-b63.score")
    assert loaded["cell"]["per_layer"] \
        == accepted["cell"]["per_layer"] + NEW
    assert loaded["cell"]["limits"] == accepted["cell"]["limits"]
    assert loaded["cell"]["data"] == accepted["cell"]["data"]
    assert loaded["cell"]["job"]["train_rows"] == 10500000


def test_traced_run_prints_the_forest_metrics():
    # a run is a process of its own; here earlier tests' spans are in the
    # ring, and "all" roots would take them in
    from lightgbm_tpu.runtime import profiler
    profiler._RECORDER.ring.clear()
    res = harness.run_cell(CELL, 2**31 + 77, 0.1, True, require_chip=False,
                           overrides=TOY)
    assert res["correct"], res["compared"]
    m = res["metrics"]
    for name in ("forest_construct_s", "forest_chunks_s", "forest_drain_s",
                 "forest_compile_s", "forest_init_s", "forest_stop_wait_s",
                 "forest_train_s"):
        assert m[name]["value"] >= 0.0 and m[name]["unit"] == "s", name
    assert m["forest_compile_s"]["value"] > 0.0       # a fresh process
    inside = sum(m[k]["value"] for k in (
        "forest_construct_s", "forest_init_s", "forest_chunks_s",
        "forest_stop_wait_s", "forest_drain_s"))
    assert 0.0 < inside <= m["forest_train_s"]["value"]
    # the host walk: casts and the rest are read, nothing was uploaded
    assert m["score_cast_ms_per_pass"]["value"] > 0.0
    assert m["score_entry_other_ms_per_pass"]["value"] > 0.0
    for name in ("score_transfer_ms_per_pass",
                 "score_wait_device_ms_per_pass", "score_idle_named_pct",
                 "score_idle_in_casts_pct"):
        assert name not in m, name


def test_training_cell_reads_its_set_up_from_the_same_spans():
    # higgs28-b63.train lists forest_construct_s, forest_init_s and
    # forest_compile_s: the spans around the calls the kind also clocks
    from conftest import TOY as TRAIN_TOY
    from lightgbm_tpu.runtime import profiler
    profiler._RECORDER.ring.clear()
    res = harness.run_cell("higgs28-b63.train", 2**31 + 79, 0.1, True,
                           require_chip=False, overrides=TRAIN_TOY)
    assert res["correct"], res["compared"]
    m, info = res["metrics"], res["info"]
    assert 0.0 < m["forest_construct_s"]["value"] \
        <= info["dataset_construct_s"]
    assert 0.0 < m["forest_init_s"]["value"] <= info["booster_init_s"]
    assert m["forest_compile_s"]["value"] >= 0.0
