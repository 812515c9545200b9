"""The span-and-counter primitive (runtime/profiler.py), where the
program opens spans, the benchmark's two span readers, and the Pallas
kernels' names. All on the CPU: spans time the host and never fence."""

import collections
import json
import os
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.runtime import profiler as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "bench"), os.path.join(ROOT, "scripts")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

PARAMS = {"objective": "binary", "num_leaves": 7, "verbose": -1,
          "min_data_in_leaf": 5}


@pytest.fixture(autouse=True)
def spans_on():
    P.set_spans(True)
    yield
    P.set_spans(True)


def _names(records):
    return [r["name"] for r in records]


def _children(records, parent):
    return [r for r in records if r["parent"] == parent["id"]]


def _last_root(name, records=None):
    """The newest finished root span called ``name`` followed by its
    descendants in start order (the benchmark reader's own selection)."""
    from readers import program_span
    found = program_span.trees(P.spans() if records is None else records,
                               name)
    if not found:
        return []
    root, kids = found[0]
    return [root] + sorted(kids, key=lambda r: (r["start_ns"], r["id"]))


def _self_ns(record, records):
    """A span's duration less what its child spans cover."""
    from trace_reduce import union_ns
    kids = [(r["start_ns"], r["end_ns"]) for r in _children(records, record)]
    return record["end_ns"] - record["start_ns"] - union_ns(kids)


@pytest.fixture(scope="module")
def table():
    rng = np.random.RandomState(3)
    X = rng.normal(size=(100_000, 6)).astype(np.float32)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.float64)
    return X, y


@pytest.fixture(scope="module")
def booster(table):
    X, y = table
    ds = lgb.Dataset(X[:5000], label=y[:5000], params=PARAMS)
    bst = lgb.Booster(params=PARAMS, train_set=ds)
    for _ in range(3):
        bst.update()
    assert bst.num_trees() == 3          # drained here, not in a predict
    return bst


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

def test_parent_and_root_ids_follow_nesting():
    with P.span("t/outer", rows=5):
        with P.span("t/mid"):
            with P.span("t/leaf"):
                pass
        with P.span("t/second"):
            pass
    tree = _last_root("t/outer")
    assert _names(tree) == ["t/outer", "t/mid", "t/leaf", "t/second"]
    outer, mid, leaf, second = tree
    assert outer["parent"] is None and outer["root"] == outer["id"]
    assert mid["parent"] == outer["id"] and second["parent"] == outer["id"]
    assert leaf["parent"] == mid["id"]
    assert {r["root"] for r in tree} == {outer["id"]}
    assert outer["counts"] == {"rows": 5}
    assert all(r["end_ns"] >= r["start_ns"] for r in tree)
    assert outer["start_ns"] <= mid["start_ns"] <= leaf["start_ns"]
    assert outer["thread"] == threading.current_thread().name


def test_each_thread_has_its_own_stack():
    inside = threading.Event()
    release = threading.Event()

    def worker():
        with P.span("t/worker"):
            inside.set()
            assert release.wait(10)

    th = threading.Thread(target=worker, name="spans-test-worker")
    with P.span("t/main"):
        th.start()
        assert inside.wait(10)
        with P.span("t/main/child"):
            pass
        release.set()
        th.join(10)
        assert not th.is_alive()
    main = _last_root("t/main")
    work = _last_root("t/worker")
    assert _names(main) == ["t/main", "t/main/child"]
    assert len(work) == 1 and work[0]["parent"] is None
    assert work[0]["thread"] == "spans-test-worker"
    assert work[0]["root"] != main[0]["root"]


def test_self_time_is_duration_less_children():
    recs = [
        {"id": 1, "parent": None, "start_ns": 0, "end_ns": 100},
        {"id": 2, "parent": 1, "start_ns": 10, "end_ns": 30},
        {"id": 3, "parent": 1, "start_ns": 50, "end_ns": 90},
        {"id": 4, "parent": 3, "start_ns": 60, "end_ns": 70},
    ]
    assert _self_ns(recs[0], recs) == 40
    assert _self_ns(recs[2], recs) == 30
    assert _self_ns(recs[3], recs) == 10


def test_ring_is_bounded_and_drops_the_oldest():
    rec = P.SpanRecorder(maxlen=8)
    for i in range(20):
        with rec.span(f"t/{i}"):
            pass
    assert _names(rec.spans()) == [f"t/{i}" for i in range(12, 20)]
    assert _last_root("t/3", rec.spans()) == []
    # set-up spans outlast a window of block-wise passes: 45 Bosch
    # passes of 4 + 6 x 19 records are 5,310
    assert P._RECORDER.ring.maxlen == P.SPAN_RING_SIZE == 16384


def test_profile_export_carries_the_ring(table):
    X, y = table
    bst = lgb.train(dict(PARAMS, device_profile=True),
                    lgb.Dataset(X[:2000], label=y[:2000]),
                    num_boost_round=1)
    with P.span("t/export", rows=2):
        pass
    out = json.loads(json.dumps(bst.get_profile()))
    assert out["spans"][-1]["name"] == "t/export"
    assert out["spans"][-1]["counts"] == {"rows": 2}
    assert out["compiles_outside_spans"] == P.compiles_outside_spans()


def test_count_adds_to_the_innermost_open_span():
    P.count(rows=1)                      # nothing open: nothing counted
    with P.span("t/count", rows=1):
        P.count(rows=2, bytes_up=10)
        with P.span("t/count/inner"):
            P.count(bytes_up=5)
        P.count(bytes_up=1)
    root, inner = _last_root("t/count")
    assert root["counts"] == {"rows": 3, "bytes_up": 11}
    assert inner["counts"] == {"bytes_up": 5}


def test_off_records_nothing_and_enters_no_annotation(monkeypatch):
    entered = []
    real = P._trace_annotation
    monkeypatch.setattr(
        P, "_trace_annotation",
        lambda name: (entered.append(name), real(name))[1])
    with P.span("t/on"):
        pass
    assert entered == ["t/on"]
    before = len(P.spans())
    P.set_spans(False)
    with P.span("t/off", rows=1):
        P.count(rows=1)
    assert entered == ["t/on"]
    assert len(P.spans()) == before and _last_root("t/off") == []
    P.set_spans(True)
    with P.span("t/on_again"):
        pass
    assert entered == ["t/on", "t/on_again"]


def test_annotation_carries_the_trace_prefix(monkeypatch):
    made = []

    class Annotation:
        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with P.span("t/prefixed"):
        pass
    assert made == ["lgbm:t/prefixed"]


def test_compile_is_counted_on_the_open_span():
    salt = float(np.random.RandomState().rand())   # a program never seen

    def fresh(x):
        return x * salt + 1.0

    with P.span("t/compile"):
        with P.span("t/compile/inner"):
            jax.jit(fresh)(jnp.ones((7,))).block_until_ready()
    root, inner = _last_root("t/compile")
    assert inner["counts"]["compiles"] >= 1
    assert inner["counts"]["compile_s"] > 0.0
    assert "compiles" not in root["counts"]
    outside = P.compiles_outside_spans()
    jax.jit(lambda x: fresh(x) * 2.0)(jnp.ones((7,))).block_until_ready()
    assert P.compiles_outside_spans() > outside


@pytest.mark.parametrize("count", ["cache_hits", "cache_misses"])
def test_cache_events_are_counted_on_the_open_span(count):
    """The persistent compile cache's own events land where `compiles`
    goes (driven through jax.monitoring: no real cache on the CPU)."""
    event = "/jax/compilation_cache/" + count
    assert P.CACHE_EVENTS[event] == count
    with P.span("t/cache"):
        with P.span("t/cache/inner"):
            jax.monitoring.record_event(event)
            jax.monitoring.record_event(event)
            jax.monitoring.record_event(
                "/jax/compilation_cache/compile_requests_use_cache")
        seen = threading.Thread(
            target=jax.monitoring.record_event, args=(event,))
        seen.start()                   # another thread has no open span
        seen.join()
    root, inner = _last_root("t/cache")
    assert inner["counts"] == {count: 2}
    assert root["counts"] == {}


def test_cache_events_go_nowhere_when_spans_are_off():
    event = "/jax/compilation_cache/cache_misses"
    with P.span("t/listener"):         # the listeners are registered
        pass
    P.set_spans(False)
    before = P.spans()
    with P.span("t/cache_off"):
        jax.monitoring.record_event(event)
    jax.monitoring.record_event(event)             # and outside any span
    assert P.spans() == before         # no record, and no count moved


def test_span_never_fences_the_device(monkeypatch):
    """No span of the primitive waits for the device."""
    def refuse():
        raise AssertionError("a span fenced the device")

    monkeypatch.setattr(P, "device_barrier", refuse)
    monkeypatch.setattr(jax, "effects_barrier", refuse)
    with P.span("t/nofence"):
        with P.span("t/nofence/inner"):
            pass
    assert len(_last_root("t/nofence")) == 2


# ---------------------------------------------------------------------------
# where the program opens spans
# ---------------------------------------------------------------------------

def test_predict_host_route_children_in_order(table, booster):
    X, _ = table
    out = booster.predict(X, raw_score=True)
    assert out.shape == (100_000,)
    tree = _last_root("predict")
    root = tree[0]
    assert _names(_children(tree, root)) == [
        "predict/to_numpy", "predict/raw", "predict/convert_output"]
    raw = next(r for r in tree if r["name"] == "predict/raw")
    assert _names(_children(tree, raw)) == [
        "predict/cast_f64", "predict/host_walk"]
    assert root["counts"] == {"rows": 100_000, "features": 6,
                              "bytes_in": X.nbytes}
    assert raw["counts"] == {"trees": 3, "device_route": 0}
    # the children cover their parents but for the glue between them
    assert _self_ns(root, tree) < 0.05 * (root["end_ns"]
                                           - root["start_ns"])
    assert len(tree) < 50


def test_predict_device_route_names_are_pinned(table, booster):
    from lightgbm_tpu.models.predictor import predict_margin_device
    X, _ = table
    trees = booster._gbdt.models
    with P.span("t/device_route"):
        got = predict_margin_device(trees, 1, X[:4096])
    want = booster.predict(X[:4096], raw_score=True)
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-6)
    tree = _last_root("t/device_route")
    assert _names(tree[1:]) == [
        "predict/tables", "predict/upload", "predict/layout",
        "predict/dispatch", "predict/wait_device", "predict/download",
        "predict/cast_out"]
    by = {r["name"]: r for r in tree}
    assert by["predict/upload"]["counts"]["bytes_up"] == 4096 * 6 * 4
    assert by["predict/download"]["counts"]["bytes_down"] == 4096 * 4
    # which body scored the rows, and its row tile: off the TPU and not
    # under the Pallas interpreter, the XLA scans (fused 0)
    dispatch = by["predict/dispatch"]["counts"]
    assert (dispatch["fused"], dispatch["row_tile"]) == (0, 4096)


def test_predict_device_route_by_blocks(table, booster, force_row_blocks):
    """A call of three row blocks, two in flight: each stage three times
    under the caller's span, block b - 2 finished before block b is
    issued; the bytes of the blocks sum to the one-block call's, and the
    span open at entry says how the table was cut."""
    from lightgbm_tpu.models import predictor
    from readers import program_span
    X, _ = table
    trees = booster._gbdt.models
    tables = predictor.build_device_tables(trees, 1, 6)

    def call(name, rows):
        with P.span(name):
            out = predictor.predict_margin_device(trees, 1, rows,
                                                  tables=tables)
        return out, _last_root(name)

    want, one = call("t/one_block", X[:3 * 4096])
    assert one[0]["counts"] == {"blocks": 1, "block_rows": 3 * 4096,
                                "in_flight": 1}
    force_row_blocks()
    got, tree = call("t/three_blocks", X[:3 * 4096])
    assert np.array_equal(got, want)
    assert tree[0]["counts"] == {"blocks": 3, "block_rows": 4096,
                                 "in_flight": 2}
    issue = ["predict/upload", "predict/layout", "predict/dispatch"]
    finish = ["predict/wait_device", "predict/download", "predict/cast_out"]
    assert _names(tree[1:]) == issue * 2 + finish + issue + finish * 2
    assert {r["parent"] for r in tree[1:]} == {tree[0]["id"]}
    for key in ("bytes_up", "layout_bytes", "bytes_down"):
        whole = sum(r["counts"].get(key, 0) for r in one)
        assert whole > 0 and whole == program_span.read(
            {}, {"root": "t/three_blocks", "count": key})
    # rows that are no whole number of blocks: the last block is sent,
    # coded and fetched whole, the rows it repeats with it
    _, ragged = call("t/ragged_blocks", X[:9000])
    by = collections.Counter()
    for r in ragged[1:]:
        by.update(r["counts"])
    assert by["bytes_up"] == 3 * 4096 * 6 * 4 > 9000 * 6 * 4
    assert by["bytes_down"] == 3 * 4096 * 4


def test_table_budget_is_beside_the_blocks_in_flight(monkeypatch, booster,
                                                     force_row_blocks):
    """The same tables on the same device: over budget beside all of a
    table's rows, within it beside the blocks that are there at once."""
    from lightgbm_tpu.models import predictor
    tables = predictor.build_device_tables(booster._gbdt.models, 1, 6)
    rows, row_bytes = 1_000_000, 4 * 6 + tables.layout_row_bytes
    monkeypatch.setattr(predictor, "_device_memory_bytes",
                        lambda: tables.nbytes + 100_000 * row_bytes)
    # 24 MB of rows are one block: all of them are resident
    assert tables.resident_rows(rows) == rows and tables.over_budget(rows)
    assert predictor.build_device_tables(booster._gbdt.models, 1, 6,
                                         rows=rows) is None
    force_row_blocks(tiles=2, in_flight=3)
    assert tables.resident_rows(rows) == 3 * 2 * 4096
    assert not tables.over_budget(rows)
    assert predictor.build_device_tables(booster._gbdt.models, 1, 6,
                                         rows=rows) is not None


def test_device_stages_carry_scope_names(monkeypatch, table, booster):
    """The predictor's three per-tree stages and the scan body's stages
    are named in the lowered programs (what xprof shows per operation)."""
    from lightgbm_tpu.models import predictor
    X, _ = table
    tabs = predictor.build_device_tables(booster._gbdt.models, 1, 6)
    codes = jnp.zeros((tabs.terms * tabs.F_pad, 8192), jnp.int8)
    for fused in (False, True):
        text = predictor._get_device_margin().lower(
            codes, *tabs.arrays, K=1, has_nan=tabs.has_nan,
            has_zero=tabs.has_zero, n=4096, fused=fused,
            interpret=fused).as_text(debug_info=True)
        for stage in ("feature_select", "path_match", "leaf_sum"):
            assert "predict/" + stage in text, (fused, stage)
    monkeypatch.setenv("LIGHTGBM_TPU_DISABLE_BATCHED", "0")
    y = (X[:3000, 0] > 0).astype(np.float64)
    ds = lgb.Dataset(X[:3000], label=y, params=PARAMS)
    g = lgb.Booster(params=PARAMS, train_set=ds)._gbdt
    scan = g._get_scan_fn(2, g._batched_sampling_mode())
    text = scan.lower(
        g.X_t, g.scores, g.label_dev, g.weight_dev,
        jnp.ones((g._host_pad,), jnp.float32), jnp.float32(0.1),
        jnp.int32(0), jnp.int32(2), jnp.ones((2, 6), bool), g.meta,
        (), (), (), (), (), ()).as_text(debug_info=True)
    for stage in ("gradients", "root_histogram", "wave_pass",
                  "split_search", "apply", "score_update"):
        assert "train/" + stage in text, stage


def test_update_batch_records_chunks_and_drain(monkeypatch, table):
    monkeypatch.setenv("LIGHTGBM_TPU_DISABLE_BATCHED", "0")
    X, y = table
    before = len([r for r in P.spans() if r["name"] == "train/chunk"])
    ds = lgb.Dataset(X[:4000], label=y[:4000], params=PARAMS)
    bst = lgb.Booster(params=PARAMS, train_set=ds)
    bst.update_batch(5, chunk=3)
    assert bst.num_trees() == 5
    recs = P.spans()
    chunks = [r for r in recs if r["name"] == "train/chunk"][before:]
    assert [c["counts"]["trees"] for c in chunks] == [3, 2]
    assert [c["counts"]["trees_padded"] for c in chunks] == [3, 3]
    assert chunks[0]["counts"]["dispatches"] == 1
    assert chunks[1]["counts"]["dispatches"] == 2     # the tail's slice
    first = [r for r in recs if r["root"] == chunks[0]["id"]]
    assert _names(sorted(first, key=lambda r: r["start_ns"])) == [
        "train/chunk", "train/chunk/prepare", "train/chunk/scan_fn",
        "train/chunk/dispatch", "train/chunk/submit"]
    drain = _last_root("train/drain")
    assert _names(drain) == ["train/drain", "train/drain/device_get",
                             "train/drain/to_trees"]
    assert drain[0]["counts"]["trees"] == 5
    assert drain[1]["counts"]["bytes_down"] > 0
    n = len(P.spans())
    bst.num_trees()                      # nothing pending: no new span
    assert len(P.spans()) == n
    construct = _last_root("dataset/construct")
    assert _names(construct) == [
        "dataset/construct", "dataset/sample", "dataset/find_bins",
        "dataset/host_bin", "dataset/finalize"]
    assert construct[0]["counts"] == {"rows": 4000, "features": 6,
                                      "binned_on_device": 0}
    init = _last_root("booster/init")
    assert _names(init) == ["booster/init", "booster/init/transpose",
                            "booster/init/upload", "booster/init/meta"]
    assert init[2]["counts"]["bytes_up"] == 4000 * 6


def test_per_iteration_training_opens_no_span_per_tree():
    """2000 iterations through train_one_iter leave the calls that came
    before them in the ring: the only spans of that path are the stop
    checks (powers of two, then every 32nd iteration) and the drain."""
    rng = np.random.RandomState(5)
    X = rng.normal(size=(64, 2)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    params = dict(PARAMS, num_leaves=2, min_data_in_leaf=1)
    P._RECORDER.ring.clear()
    bst = lgb.Booster(params=params,
                      train_set=lgb.Dataset(X, label=y, params=params))
    for _ in range(2000):
        bst.update()
    assert bst.num_trees() == 2000
    recs = P.spans()
    assert _names(_last_root("dataset/construct"))[0] == "dataset/construct"
    assert _names(_last_root("booster/init"))[0] == "booster/init"
    roots = collections.Counter(r["name"] for r in recs
                                if r["parent"] is None)
    assert set(roots) == {"dataset/construct", "booster/init",
                          "train/stop_check", "train/drain"}
    assert roots["train/stop_check"] <= 2000 // 32 + 12
    assert len(recs) < 200


def test_stage_profiler_json_unchanged_and_spans_ride_along(table):
    clock = iter(float(i) for i in range(100))
    prof = P.StageProfiler(clock=lambda: next(clock), barrier=lambda: None)
    prof.iter_start()
    with prof.span("grow"):
        pass
    prof.iter_end(n_rows=10)
    d = prof.to_dict()
    assert set(d) == {"n_iters", "total_wall_s", "stages_s",
                      "stage_counts", "ring", "row_iters_per_sec"}
    assert d["stages_s"] == {"other": 2.0, "grow": 1.0}
    assert d["ring"] == [{"iter": 0, "wall_s": 3.0,
                          "stages_s": {"grow": 1.0, "other": 2.0}}]
    assert _last_root("grow") == []          # per tree: its own ring only
    with prof.span("bin"):                   # outside an iteration
        pass
    assert _last_root("bin")[0]["name"] == "bin"      # one recorder
    quiet = P.StageProfiler(barrier=lambda: None, record_spans=False)
    n = len(P.spans())
    with quiet.span("score"):
        pass
    assert len(P.spans()) == n and quiet.counts["score"] == 1
    X, y = table
    bst = lgb.train(dict(PARAMS, device_profile=True),
                    lgb.Dataset(X[:2000], label=y[:2000]),
                    num_boost_round=2)
    names = {r["name"] for r in bst.get_profile()["spans"]}
    assert {"booster/init", "bin"} <= names and "grow" not in names


def test_timer_and_its_switch_are_gone():
    import lightgbm_tpu.runtime as rt
    for gone in ("Timer", "global_timer", "trace"):
        assert not hasattr(P, gone) and not hasattr(rt, gone)
    with pytest.raises(ImportError):
        import lightgbm_tpu.utils.timer  # noqa: F401


# ---------------------------------------------------------------------------
# the benchmark's readers, on synthetic spans
# ---------------------------------------------------------------------------

def _rec(i, name, parent, root, s, e, **counts):
    return {"name": name, "id": i, "parent": parent, "root": root,
            "start_ns": s, "end_ns": e, "thread": "MainThread",
            "counts": counts}


MS = 1_000_000
SYNTHETIC = [
    _rec(1, "train/chunk", None, 1, 0, 4 * MS, trees=3),
    _rec(2, "train/chunk/dispatch", 1, 1, 1 * MS, 3 * MS, compile_s=0.5),
    _rec(3, "train/chunk", None, 3, 5 * MS, 7 * MS, trees=3),
    _rec(4, "predict", None, 4, 1000 * MS, 1100 * MS, rows=9),
    _rec(5, "predict/to_numpy", 4, 4, 1000 * MS, 1002 * MS),
    _rec(6, "predict/raw", 4, 4, 1002 * MS, 1098 * MS),
    _rec(7, "predict/cast_f64", 6, 4, 1002 * MS, 1022 * MS),
    _rec(8, "predict/cast_f32", 6, 4, 1022 * MS, 1032 * MS),
    _rec(9, "predict/upload", 6, 4, 1032 * MS, 1040 * MS),
    _rec(10, "predict/wait_device", 6, 4, 1040 * MS, 1090 * MS),
    _rec(11, "predict/download", 6, 4, 1090 * MS, 1094 * MS),
    _rec(12, "predict/cast_out", 6, 4, 1094 * MS, 1098 * MS),
    _rec(13, "predict/convert_output", 4, 4, 1098 * MS, 1100 * MS),
    _rec(14, "booster/init", None, 14, 8 * MS, 9 * MS, compile_s=0.25),
    _rec(15, "train/stop_check", None, 15, 4 * MS, 5 * MS),
    _rec(16, "train/stop_check", None, 16, 7 * MS, 10 * MS),
]


@pytest.fixture
def synthetic(monkeypatch):
    monkeypatch.setattr(P, "spans", lambda: list(SYNTHETIC))


CAST = ["^predict/cast_"]
XFER = ["^predict/(upload|layout|download)$"]
WAIT = ["^predict/wait_device$"]


@pytest.mark.parametrize("params, want", [
    ({"root": "predict", "match": CAST, "scale": 1000.0}, 34.0),
    ({"root": "predict", "match": XFER, "scale": 1000.0}, 12.0),
    ({"root": "predict", "match": WAIT, "scale": 1000.0}, 50.0),
    ({"root": "predict", "minus": CAST + XFER + WAIT, "scale": 1000.0},
     4.0),
    ({"root": "predict", "match": ["^predict/"], "scale": 1000.0}, 100.0),
    ({"root": "train/chunk", "which": "all"}, 0.006),
    ({"root": "train/chunk", "which": "last"}, 0.002),
    ({"root": ["train/chunk", "booster/init"], "which": "all",
      "count": "compile_s"}, 0.75),
    ({"root": "booster/init", "which": "all"}, 0.001),
    ({"root": "train/stop_check", "which": "all"}, 0.004),
    ({"root": "train/drain", "which": "all"}, None),
    ({"root": "predict", "match": ["^predict/layout$"]}, None),
    ({"root": "predict/raw"}, None),       # not a root here
])
def test_program_span_reader(synthetic, params, want):
    from readers import program_span
    got = program_span.read({}, params)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-12)


def test_program_span_reader_without_a_recorder(monkeypatch):
    from readers import program_span, span_gap
    monkeypatch.delattr(P, "spans")
    assert program_span.read({}, {"root": "predict"}) is None
    assert span_gap.read({"trace": None}, {"match": ["."]}) is None


def _reduction(gap_at, window=(5000 * MS, 5100 * MS)):
    """A TraceReduction from a hand-made trace dict: one device, busy
    all through the window but for one gap of 20 ms."""
    import trace_reduce
    g0, g1 = gap_at
    w0, w1 = window
    trace = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench:traced_pass", w0, w1 - w0]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["fusion.1", w0, g0 - w0], ["fusion.2", g1, w1 - g1]]}]}]}
    return trace_reduce.reduce(trace, window_span="bench:traced_pass")


def test_span_gap_reader_half_covered_reads_50(synthetic):
    from readers import span_gap
    # on the window's clock the casts end at +32 ms: a gap from +22 to
    # +42 ms lies half under cast_f32, half under upload and wait_device
    red = _reduction((5022 * MS, 5042 * MS))
    assert red.devices[0].gaps()[0] == (5022 * MS, 20 * MS)
    ctx = {"trace": red}
    assert span_gap.read(ctx, {"root": "predict", "match": CAST}) \
        == pytest.approx(50.0)
    assert span_gap.read(ctx, {"root": "predict", "match": ["."]}) \
        == pytest.approx(100.0)
    assert span_gap.read(ctx, {"root": "predict", "match": XFER}) \
        == pytest.approx(40.0)
    # a parent span is no leaf: predict/raw alone names nothing
    assert span_gap.read(ctx, {"root": "predict",
                               "match": ["^predict/raw$"]}) == 0.0


@pytest.mark.parametrize("window", [
    (5000 * MS, 5099 * MS),              # the call is longer than it
    (5000 * MS, 5102 * MS),              # over 1 % longer than the call
])
def test_span_gap_reader_gives_up_on_a_window_that_is_not_the_call(
        synthetic, window):
    from readers import span_gap
    red = _reduction((5022 * MS, 5042 * MS), window)
    assert span_gap.read({"trace": red},
                         {"root": "predict", "match": ["."]}) is None


# ---------------------------------------------------------------------------
# kernel names
# ---------------------------------------------------------------------------

def test_every_pallas_site_carries_an_explicit_distinct_name():
    import kernel_check
    rng = np.random.RandomState(0)
    by_case = {}
    for case, build in kernel_check.cases():
        make, fn_of = build()
        by_case[case] = P.pallas_kernel_names(fn_of(False), *make(rng))
    assert all(by_case.values()), by_case
    names = {n for ns in by_case.values() for n in ns}
    assert all(n.startswith("lgbm_") for n in names), names
    variant = re.compile(r"(_(k|b|lo|t|l|f|m|n|w|c)\d+|_q|_nan|_zero)*$")
    families = {variant.sub("", n) for n in names}
    assert families == {
        "lgbm_hist_slots", "lgbm_take_leaf_values", "lgbm_wave_pass",
        "lgbm_wave_apply", "lgbm_wave_relabel", "lgbm_hist_rowwise",
        "lgbm_hist_rowwise_packed", "lgbm_bucketize",
        "lgbm_predict_forest"}, families
    # one name per compiled variant: bins, hi/lo split and operand type
    variants = [by_case[c][0] for c in (
        "megakernel B64 f32", "megakernel B64 int8",
        "megakernel hi/lo B256 f32", "slots legacy F28 B256 f32",
        "slots hi/lo F28 B256 f32", "predict_forest L255 F28",
        "predict_forest L15 F130 K3 nan", "predict_forest L31 F28 cat",
        "predict_forest L255 F4 nan c2")]
    assert len(set(variants)) == len(variants), variants
    from lightgbm_tpu.ops.histogram_pallas import wave_pass_pallas
    make, _ = kernel_check._mega(64, False, 128)
    X, v, lor, t = make(rng)
    per_k = {P.pallas_kernel_names(
        lambda *a, K=K: wave_pass_pallas(*a, K, 64), X, v, lor, t)[0]
        for K in (1, 2, 4, 8, 16, 32, 64, 128)}
    assert len(per_k) == 8, per_k
