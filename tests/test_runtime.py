"""Runtime subsystem tests: stage profiling + strategy autotuning
(lightgbm_tpu/runtime/).

Profiling contracts: per-iteration spans are device-fenced, non-negative
and monotone in accumulation, and the per-stage breakdown sums to the
measured wall time (the "other" catch-all guarantees it by construction
— these tests pin that invariant so a refactor can't silently drop it).

Autotune contracts: deterministic under a fixed probe seed + injected
clock, decision cache round-trips to disk, and autotune=false (or a
cache pre-seeded with the ladder's own choice) reproduces today's
dispatch bit-for-bit.
"""

import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.runtime import autotune as at
from lightgbm_tpu.runtime.profiler import StageProfiler


@pytest.fixture
def binary_data(rng):
    X = rng.normal(size=(1200, 6))
    y = (X[:, 0] + X[:, 1] > 0).astype(int)
    return X, y


@pytest.fixture(autouse=True)
def _isolate_autotune_cache(tmp_path, monkeypatch):
    """Keep every test's decisions out of the user-level disk cache and
    out of other tests' in-process cache."""
    monkeypatch.setenv("LIGHTGBM_TPU_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    saved = dict(at._MEM_CACHE)
    at._MEM_CACHE.clear()
    yield
    at._MEM_CACHE.clear()
    at._MEM_CACHE.update(saved)


PARAMS = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
          "min_data_in_leaf": 5, "seed": 7}


# ---------------------------------------------------------------------------
# profiler


def test_stage_profiler_other_closes_the_wall():
    """Synthetic clock: explicit spans + "other" must sum exactly to the
    iteration wall, and unspanned time lands in "other"."""
    t = [0.0]

    def clock():
        return t[0]

    prof = StageProfiler(clock=clock, barrier=lambda: None)
    prof.iter_start()
    t[0] += 1.0                      # unspanned host time
    with prof.span("grow"):
        t[0] += 3.0
    with prof.span("boost"):
        t[0] += 0.5
    prof.iter_end(n_rows=100)

    (rec,) = prof.ring
    assert rec["wall_s"] == pytest.approx(4.5)
    assert rec["stages_s"]["grow"] == pytest.approx(3.0)
    assert rec["stages_s"]["boost"] == pytest.approx(0.5)
    assert rec["stages_s"]["other"] == pytest.approx(1.0)
    assert sum(rec["stages_s"].values()) == pytest.approx(rec["wall_s"])
    assert prof.row_iters_per_sec() == pytest.approx(100 / 4.5)


def test_iter_meta_lands_in_current_ring_record():
    """iter_meta fields merge into the ACTIVE iteration's record only:
    a no-op outside an iteration, reset for the next one."""
    prof = StageProfiler(clock=lambda: 0.0, barrier=lambda: None)
    prof.iter_meta(comm_mode="lost")        # outside: dropped
    prof.iter_start()
    prof.iter_meta(comm_mode="reduce_scatter", comm_bytes=4096)
    prof.iter_end()
    prof.iter_start()
    prof.iter_end()
    first, second = prof.ring
    assert first["comm_mode"] == "reduce_scatter"
    assert first["comm_bytes"] == 4096
    assert "comm_mode" not in second and "comm_bytes" not in second


def test_comm_fields_in_distributed_profile(binary_data):
    """Data-parallel training with profiling exports comm_mode /
    comm_bytes on every iteration record (docs/PERF.md section 5), the
    run-total counter, and the analytic wire profile in extras."""
    X, y = binary_data
    bst = lgb.train(dict(PARAMS, device_profile=True, tree_learner="data",
                         parallel_hist_mode="reduce_scatter"),
                    lgb.Dataset(X, label=y), num_boost_round=3)
    p = bst.get_profile()
    assert p is not None and len(p["ring"]) == 3
    for rec in p["ring"]:
        assert rec["comm_mode"] == "reduce_scatter"
        assert rec["comm_bytes"] > 0
    assert p["counters"]["comm_bytes"] == pytest.approx(
        sum(rec["comm_bytes"] for rec in p["ring"]))
    comm = p["comm"]
    assert comm["comm_mode"] == "reduce_scatter"
    assert comm["mesh_size"] > 1
    assert comm["comm_bytes_per_tree"] > 0


def test_no_comm_fields_on_serial_profile(binary_data):
    """Single-mesh training has no histogram exchange: records must not
    grow comm fields."""
    X, y = binary_data
    bst = lgb.train(dict(PARAMS, device_profile=True),
                    lgb.Dataset(X, label=y), num_boost_round=2)
    p = bst.get_profile()
    assert all("comm_mode" not in rec and "comm_bytes" not in rec
               for rec in p["ring"])
    assert "comm" not in p


def test_profile_spans_sum_to_wall_on_cpu(binary_data):
    """Real CPU-backend training: every iteration's stage breakdown sums
    to its wall time (within the acceptance bar's 20%), spans are
    non-negative, and totals are monotone over iterations."""
    X, y = binary_data
    bst = lgb.train(dict(PARAMS, device_profile=True),
                    lgb.Dataset(X, label=y), num_boost_round=5)
    p = bst.get_profile()
    assert p is not None and p["n_iters"] == 5
    assert len(p["ring"]) == 5
    prev_wall = 0.0
    for rec in p["ring"]:
        assert rec["wall_s"] >= 0.0
        assert all(v >= 0.0 for v in rec["stages_s"].values())
        ssum = sum(rec["stages_s"].values())
        assert ssum == pytest.approx(rec["wall_s"], rel=0.2)
        prev_wall += rec["wall_s"]
    assert p["total_wall_s"] == pytest.approx(prev_wall, rel=1e-6)
    # per-iteration stages observed by the host fence
    assert "grow" in p["stages_s"] and "boost" in p["stages_s"]
    # init-scope upload span accumulates into totals only
    assert "bin" in p["stages_s"]
    assert p["row_iters_per_sec"] > 0
    # one-time fused-kernel decomposition probe
    assert set(p["stage_probe"]) >= {"histogram_s", "split_search_s",
                                     "partition_s"}


def test_record_profile_callback(binary_data):
    X, y = binary_data
    result = {}
    lgb.train(dict(PARAMS, device_profile=True), lgb.Dataset(X, label=y),
              num_boost_round=4, callbacks=[lgb.record_profile(result)])
    assert len(result["wall_s"]) == 4
    assert len(result["stages_s"]["grow"]) == 4
    assert result["profile"]["n_iters"] == 4


def test_no_profiler_without_flag(binary_data):
    X, y = binary_data
    bst = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                    num_boost_round=2)
    assert bst.get_profile() is None


# ---------------------------------------------------------------------------
# autotune


def _fake_clock():
    """Deterministic clock: each call advances 1s, so every probe measures
    exactly 1s and candidates tie — the tie resolves by preference order,
    deterministically."""
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    return clock


def _probe_inputs(binary_data):
    X, y = binary_data
    bst = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                    num_boost_round=1)
    g = bst._gbdt
    return g.X_t, g.meta, g.grow_cfg


def test_autotune_deterministic_under_fixed_seed(binary_data, tmp_path):
    X_t, meta, cfg = _probe_inputs(binary_data)
    kw = dict(n_rows=1200, n_features=6, max_bin=255, num_leaves=7,
              probe_rows=512, seed=7, timer=_fake_clock())
    d1 = at.autotune_decision(X_t, meta, cfg, ["wave", "compact", "masked"],
                              cache_path=str(tmp_path / "c1.json"), **kw)
    at._MEM_CACHE.clear()
    kw["timer"] = _fake_clock()
    d2 = at.autotune_decision(X_t, meta, cfg, ["wave", "compact", "masked"],
                              cache_path=str(tmp_path / "c2.json"), **kw)
    assert d1["grower"] == d2["grower"] == "wave"   # tie -> preference
    assert d1["rows_per_chunk"] == d2["rows_per_chunk"] \
        == cfg.rows_per_chunk                       # tie -> keep configured
    assert d1["timings"] == d2["timings"]
    assert d1["key"] == d2["key"]


def test_autotune_cache_roundtrips_to_disk(binary_data, tmp_path):
    X_t, meta, cfg = _probe_inputs(binary_data)
    path = str(tmp_path / "cache.json")
    kw = dict(n_rows=1200, n_features=6, max_bin=255, num_leaves=7,
              probe_rows=512, seed=7, timer=_fake_clock(),
              tune_chunks=False)
    d1 = at.autotune_decision(X_t, meta, cfg, ["compact", "masked"],
                              cache_path=path, **kw)
    assert d1["cached"] is False
    assert os.path.exists(path)
    on_disk = json.load(open(path))
    assert on_disk[d1["key"]]["grower"] == d1["grower"]

    # fresh process simulation: memory cache cleared, disk survives
    at._MEM_CACHE.clear()

    def exploding_timer():
        raise AssertionError("cache hit must not re-probe")

    d2 = at.autotune_decision(X_t, meta, cfg, ["compact", "masked"],
                              cache_path=path, n_rows=1200, n_features=6,
                              max_bin=255, num_leaves=7, probe_rows=512,
                              seed=7, timer=exploding_timer,
                              tune_chunks=False)
    assert d2["cached"] == "disk"
    assert d2["grower"] == d1["grower"]
    # and now it's in memory too
    d3 = at.autotune_decision(X_t, meta, cfg, ["compact", "masked"],
                              cache_path=path, n_rows=1200, n_features=6,
                              max_bin=255, num_leaves=7, probe_rows=512,
                              seed=7, timer=exploding_timer,
                              tune_chunks=False)
    assert d3["cached"] == "memory"


def test_stale_cache_entry_naming_a_removed_impl_is_reprobed(binary_data,
                                                            tmp_path):
    """A cache file is input from outside the program. An entry an older
    version wrote with hist_impl='fused' (a kernel family that is gone),
    or under a key with the old '_t64rf1' variant suffix, must read as a
    miss: the shape is probed again, the decision names a surviving impl,
    the grower never sees 'fused', and training proceeds."""
    X, y = binary_data
    path = str(tmp_path / "old_cache.json")
    key = at.make_key(len(y), 6, 255, PARAMS["num_leaves"])
    old = {"grower": "wave", "rows_per_chunk": 8192, "hist_impl": "fused",
           "timings": {}, "chunk_timings": {}, "hist_impl_timings": {},
           "fused_wave_timings": {"two_pass": 0.2, "fused": 0.1},
           "key": key, "probe_rows": 0}
    with open(path, "w") as fh:
        json.dump({key: old,
                   key + "_t64rf1": dict(old, key=key + "_t64rf1")}, fh)
    bst = lgb.train(dict(PARAMS, autotune=True, autotune_cache=path),
                    lgb.Dataset(X, label=y), num_boost_round=3)
    d = bst._gbdt.autotune_decision
    assert d["cached"] is False and d["key"] == key
    assert d["hist_impl"] in (None, *at.HIST_IMPL_CANDIDATES)
    assert bst._gbdt.grow_cfg.hist_impl in ("auto", *at.HIST_IMPL_CANDIDATES)
    assert len(d["timings"]) >= 2            # the probes really ran
    assert json.load(open(path))[key]["hist_impl"] == d["hist_impl"]
    assert np.mean((bst.predict(X) > 0.5) == (y > 0.5)) > 0.9


def test_pick_winner_prefers_ladder_order_on_tie():
    assert at._pick_winner({"masked": 1.0, "compact": 1.0, "wave": 1.0},
                           at.AUTOTUNE_PREFERENCE) == "wave"
    assert at._pick_winner({"masked": 1.0, "compact": 2.0, "wave": 2.0},
                           at.AUTOTUNE_PREFERENCE) == "masked"
    # within 2% = tie
    assert at._pick_winner({"masked": 1.0, "wave": 1.01},
                           at.AUTOTUNE_PREFERENCE) == "wave"
    assert at._pick_winner({}, at.AUTOTUNE_PREFERENCE) is None


def test_autotune_off_reproduces_dispatch_bit_for_bit(binary_data):
    """autotune=false (and absent) must produce byte-identical models to
    the seed behavior, and autotune=true with a cache pre-seeded to the
    ladder's own choice must route through the autotuner without changing
    a single byte either."""
    X, y = binary_data
    base = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                     num_boost_round=5)
    off = lgb.train(dict(PARAMS, autotune=False), lgb.Dataset(X, label=y),
                    num_boost_round=5)
    s_base = base.model_to_string()
    assert off.model_to_string() == s_base
    assert base._gbdt.autotune_decision is None

    # pre-seed the decision cache with the ladder's own choice so the
    # probe result is pinned; training must match bit-for-bit
    g = base._gbdt
    key = at.make_key(g.num_data, 6, 255, PARAMS["num_leaves"])
    at._MEM_CACHE[key] = {"grower": g.grower,
                          "rows_per_chunk": g.grow_cfg.rows_per_chunk,
                          "timings": {}, "chunk_timings": {}, "key": key,
                          "probe_rows": 0}
    on = lgb.train(dict(PARAMS, autotune=True), lgb.Dataset(X, label=y),
                   num_boost_round=5)
    assert on._gbdt.autotune_decision["cached"] == "memory"
    assert on._gbdt.grower == g.grower
    # the params dump at the file tail records autotune itself; everything
    # else — every tree byte — must match
    def _strip_flag(s):
        return s.replace("[autotune: 1]", "[autotune: 0]")
    assert _strip_flag(on.model_to_string()) == _strip_flag(s_base)


def test_autotune_live_probes_select_and_train(binary_data):
    """Live probes (real clock) pick SOME feasible strategy and training
    completes with sane quality; the chosen grower is recorded."""
    X, y = binary_data
    bst = lgb.train(dict(PARAMS, autotune=True), lgb.Dataset(X, label=y),
                    num_boost_round=6)
    d = bst._gbdt.autotune_decision
    assert d is not None and d["grower"] in ("wave", "compact", "masked")
    assert set(d["timings"]) <= {"wave", "compact", "masked"}
    assert len(d["timings"]) >= 2
    pred = bst.predict(X)
    assert np.mean((pred > 0.5) == (y > 0.5)) > 0.9


def test_autotune_warns_when_constrained(binary_data):
    """A forced tpu_grower keeps the ladder choice (autotune skipped)."""
    X, y = binary_data
    bst = lgb.train(dict(PARAMS, autotune=True, tpu_grower="masked"),
                    lgb.Dataset(X, label=y), num_boost_round=2)
    assert bst._gbdt.autotune_decision is None
    assert bst._gbdt.grower == "masked"


def test_autotune_comm_probe_on_mesh(binary_data, tmp_path):
    """On a data-parallel mesh the grower autotune is constrained, but
    the histogram-exchange probe still runs, resolves auto to a concrete
    mode, and caches under the shape+mesh key (docs/PERF.md section 5)."""
    X, y = binary_data
    cache = tmp_path / "tune.json"
    bst = lgb.train(dict(PARAMS, autotune=True, tree_learner="data",
                         autotune_cache=str(cache)),
                    lgb.Dataset(X, label=y), num_boost_round=2)
    d = bst._gbdt.autotune_decision
    assert d is not None
    assert d["parallel_hist_mode"] in ("allreduce", "reduce_scatter")
    assert set(d["comm_timings"]) == {"allreduce", "reduce_scatter"}
    assert d["key"].endswith(f"_mesh{bst._gbdt.n_shards}")
    assert bst._gbdt.grow_cfg.parallel_hist_mode == d["parallel_hist_mode"]
    # second construction is a cache hit, not a re-probe
    bst2 = lgb.train(dict(PARAMS, autotune=True, tree_learner="data",
                          autotune_cache=str(cache)),
                     lgb.Dataset(X, label=y), num_boost_round=1)
    assert bst2._gbdt.autotune_decision.get("cached") in ("memory", "disk")


# ---------------------------------------------------------------------------
# CLI --profile smoke (keeps the profiling path wired into tier-1)


def test_cli_profile_smoke(tmp_path, capsys):
    from lightgbm_tpu.cli import main as cli_main
    rng = np.random.RandomState(3)
    X = rng.normal(size=(400, 5))
    y = (X[:, 0] > 0).astype(int)
    train_path = tmp_path / "train.tsv"
    np.savetxt(train_path, np.column_stack([y, X]), delimiter="\t",
               fmt="%.8g")
    out_json = tmp_path / "profile.json"
    assert cli_main([
        "task=train", "objective=binary", f"data={train_path}",
        "num_iterations=3", "num_leaves=5", "verbosity=-1",
        f"output_model={tmp_path / 'model.txt'}",
        f"profile_output={out_json}", "--profile"]) == 0

    # stdout carries the profile JSON; the file matches it
    text = capsys.readouterr().out
    start = text.index("{")
    prof = json.loads(text[start:text.rindex("}") + 1])
    assert prof == json.load(open(out_json))
    assert prof["n_iters"] == 3
    # acceptance bar: per-stage sum within 20% of measured wall time
    per_iter = [s for s in prof["stages_s"]
                if s not in ("bin", "autotune")]
    ssum = sum(prof["stages_s"][s] for s in per_iter)
    assert abs(ssum - prof["total_wall_s"]) <= 0.2 * prof["total_wall_s"]


def test_autotune_binning_decision_caches(tmp_path):
    """binning_impl=auto probe (PR 20): decision is a valid impl, disk
    cache round-trips, and unpackable mapper sets resolve to None
    (caller falls back to host)."""
    from lightgbm_tpu.data.binning import BinMapper

    rng = np.random.RandomState(5)
    mappers = [
        BinMapper.find_bin(rng.normal(size=2000), 2000, 63, 3, 20)
        for _ in range(4)]
    path = str(tmp_path / "bin_cache.json")
    d1 = at.autotune_binning_decision(
        mappers, n_rows=2000, n_features=4, max_bin=63, num_leaves=31,
        cache_path=path)
    assert d1["binning_impl"] in ("host", "device")
    assert d1["cached"] is False
    assert d1["key"].endswith("_binning")
    assert set(d1["binning_timings"]) == {"host", "device"}
    d2 = at.autotune_binning_decision(
        mappers, n_rows=2000, n_features=4, max_bin=63, num_leaves=31,
        cache_path=path)
    assert d2["cached"] == "memory"
    assert d2["binning_impl"] == d1["binning_impl"]
    at._MEM_CACHE.clear()
    d3 = at.autotune_binning_decision(
        mappers, n_rows=2000, n_features=4, max_bin=63, num_leaves=31,
        cache_path=path)
    assert d3["cached"] == "disk"
    assert d3["binning_impl"] == d1["binning_impl"]
