"""Packed predictor: batch/single-row/early-stop/device parity with the
per-tree host walk (reference semantics: gbdt_prediction.cpp,
prediction_early_stop.cpp, c_api.h:1399 single-row fast path)."""

import numpy as np
import pytest

import lightgbm_tpu as lgb


def _per_tree_margin(g, X):
    K = g.num_tree_per_iteration
    out = np.zeros((K, X.shape[0]), np.float64)
    for i, t in enumerate(g.models):
        out[i % K] += t.predict(X)
    return out


@pytest.fixture(scope="module")
def binary_model(rng_mod):
    rng = rng_mod
    X = rng.normal(size=(4000, 10)).astype(np.float32)
    w = rng.normal(size=10)
    y = (X @ w + rng.normal(scale=0.3, size=4000) > 0).astype(np.float32)
    X[::11, 3] = np.nan
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbose": -1}, ds, num_boost_round=12)
    return bst, X


@pytest.fixture(scope="module")
def rng_mod():
    return np.random.RandomState(17)


def test_packed_matches_per_tree(binary_model):
    bst, X = binary_model
    g = bst._gbdt
    ref = _per_tree_margin(g, X[:500])
    got = g.predict_raw(X[:500])
    np.testing.assert_allclose(got, ref, rtol=1e-12)


def test_single_row_fast_path(binary_model):
    bst, X = binary_model
    g = bst._gbdt
    for r in (0, 3, 11):
        ref = _per_tree_margin(g, X[r:r + 1])[:, 0]
        got = g.predict_single_row(X[r])
        np.testing.assert_allclose(got, ref, rtol=1e-12)


def test_early_stop_margin_huge_is_exact(binary_model):
    bst, X = binary_model
    g = bst._gbdt
    full = g.predict_raw(X[:400])
    es = g.predict_raw(X[:400], pred_early_stop=True,
                       pred_early_stop_freq=4,
                       pred_early_stop_margin=1e30)
    np.testing.assert_allclose(es, full, rtol=1e-12)


def test_early_stop_small_margin_keeps_confident_sign(binary_model):
    bst, X = binary_model
    g = bst._gbdt
    full = g.predict_raw(X[:1000])[0]
    es = g.predict_raw(X[:1000], pred_early_stop=True,
                       pred_early_stop_freq=2,
                       pred_early_stop_margin=0.5)[0]
    # rows stopped early halted with a margin beyond the bound (the
    # approximation the reference makes, prediction_early_stop.cpp:30);
    # rows never stopped are exact (up to f64 summation-order ulps)
    stopped = np.abs(es - full) > 1e-9
    assert stopped.any()
    assert np.all(np.abs(es[stopped]) >= 0.5)
    # and predict() plumbs the params through
    p_es = bst.predict(X[:1000], raw_score=True, pred_early_stop=True,
                       pred_early_stop_freq=2, pred_early_stop_margin=0.5)
    np.testing.assert_allclose(p_es, es, rtol=1e-12)


def test_multiclass_early_stop_and_single(rng_mod):
    rng = rng_mod
    X = rng.normal(size=(3000, 8)).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1] > 0).astype(int) + \
        2 * (X[:, 2] > 0.5).astype(int)
    ds = lgb.Dataset(X, label=y.astype(np.float32))
    bst = lgb.train({"objective": "multiclass", "num_class": 4,
                     "num_leaves": 7, "verbose": -1}, ds,
                    num_boost_round=6)
    g = bst._gbdt
    ref = _per_tree_margin(g, X[:200])
    np.testing.assert_allclose(g.predict_raw(X[:200]), ref, rtol=1e-12)
    np.testing.assert_allclose(g.predict_single_row(X[5]), ref[:, 5],
                               rtol=1e-12)
    es = g.predict_raw(X[:200], pred_early_stop=True,
                       pred_early_stop_freq=2,
                       pred_early_stop_margin=1e30)
    np.testing.assert_allclose(es, ref, rtol=1e-12)


def test_categorical_packed_parity(rng_mod):
    rng = rng_mod
    N = 3000
    Xc = rng.randint(0, 12, size=(N, 1)).astype(np.float32)
    Xn = rng.normal(size=(N, 3)).astype(np.float32)
    X = np.concatenate([Xc, Xn], axis=1)
    y = ((Xc[:, 0] % 3 == 0) ^ (Xn[:, 0] > 0)).astype(np.float32)
    ds = lgb.Dataset(X, label=y, categorical_feature=[0])
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbose": -1, "min_data_in_leaf": 5}, ds,
                    num_boost_round=8)
    g = bst._gbdt
    ref = _per_tree_margin(g, X[:300])
    np.testing.assert_allclose(g.predict_raw(X[:300]), ref, rtol=1e-12)


def test_device_predictor_parity(binary_model):
    import jax.numpy as jnp
    from lightgbm_tpu.models.predictor import predict_margin_device
    bst, X = binary_model
    g = bst._gbdt
    ref = _per_tree_margin(g, X[:256])
    got = np.asarray(predict_margin_device(
        g.models, g.num_tree_per_iteration, jnp.asarray(X[:256])))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=1e-6)


def test_device_predictor_parity_with_nan_and_cat():
    rng = np.random.RandomState(3)
    N = 2000
    Xc = rng.randint(0, 12, size=(N, 1)).astype(np.float64)
    Xn = rng.normal(size=(N, 4))
    X = np.concatenate([Xc, Xn], axis=1)
    X[::17, 2] = np.nan
    y = ((Xc[:, 0] % 3 == 0) ^ (Xn[:, 0] > 0)).astype(np.float32)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbose": -1, "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y, categorical_feature=[0]),
                    num_boost_round=8)
    g = bst._gbdt
    from lightgbm_tpu.models.predictor import predict_margin_device
    ref = _per_tree_margin(g, X[:512])
    got = np.asarray(predict_margin_device(
        g.models, g.num_tree_per_iteration, X[:512]))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the fused device kernel (predictor._forest_pallas), under the interpreter
# ---------------------------------------------------------------------------

def _walk_f32(trees, K, X):
    """The float32 reference walk: each tree's leaf by the host walk (f32
    values against the f64 thresholds), its leaf value rounded to float32
    and added tree by tree in float32."""
    out = np.zeros((K, X.shape[0]), np.float32)
    X64 = X.astype(np.float64)
    for i, t in enumerate(trees):
        out[i % K] += t.leaf_value.astype(np.float32)[t.get_leaf_index(X64)]
    return out


def _removed_xla_body(trees, K, X):
    """The three-stage XLA body the kernel replaced, kept as a reference:
    float32 one-hot feature select, float32 compare against the floored
    thresholds, float32 path matmul and the leaf value through a third
    contraction, all at precision highest. (No reference for infinite
    values: its select multiplies them by zero.)"""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.models import predictor
    from lightgbm_tpu.models.tree import (MISSING_NAN, MISSING_ZERO,
                                          _CATEGORICAL_MASK,
                                          _DEFAULT_LEFT_MASK,
                                          _KZERO_THRESHOLD)
    hp = jax.lax.Precision.HIGHEST
    F = X.shape[1]
    M_pad, L_pad, n_bias = predictor._padded_shape(trees)
    W = max([int(np.diff(t.cat_boundaries).max()) for t in trees
             if t.num_cat > 0], default=0)
    Xt = jnp.asarray(X).T
    nan_f = jnp.isnan(Xt)
    Xclean = jnp.where(nan_f, 0.0, Xt)
    outs = [jnp.zeros(X.shape[0], jnp.float32) for _ in range(K)]
    for i, tree in enumerate(trees):
        P, feat, thr, dt, bits, lv = predictor._tree_path_tables(
            tree, M_pad, L_pad, W, n_bias)
        ohf = jnp.asarray(feat[:, None] == np.arange(F), jnp.float32)
        thr, dt = jnp.asarray(thr)[:, None], jnp.asarray(dt)[:, None]
        bits = jnp.asarray(bits.view(np.int32))
        fval = jnp.dot(ohf, Xclean, precision=hp)
        nan_mask = jnp.dot(ohf, nan_f.astype(jnp.float32),
                           precision=hp) > 0.5
        mt = (dt >> 2) & 3
        fval_n = jnp.where(nan_mask, 0.0, fval)
        is_missing = ((mt == MISSING_ZERO)
                      & (jnp.abs(fval_n) <= _KZERO_THRESHOLD)) | \
                     ((mt == MISSING_NAN) & nan_mask)
        go_left = jnp.where(is_missing, (dt & _DEFAULT_LEFT_MASK) != 0,
                            fval_n <= thr)
        if W > 0:
            valid = ~nan_mask & (fval >= 0)
            iv = jnp.where(valid, fval, 0).astype(jnp.int32)
            widx = jnp.clip(iv >> 5, 0, W - 1)
            wsel = jnp.zeros(iv.shape, jnp.int32)
            for w in range(W):
                wsel = jnp.where(widx == w, bits[:, w:w + 1], wsel)
            gl_cat = valid & (iv < W * 32) & (((wsel >> (iv & 31)) & 1) == 1)
            go_left = jnp.where((dt & _CATEGORICAL_MASK) != 0, gl_cat,
                                go_left)
        counts = jnp.dot(jnp.asarray(P, jnp.float32),
                         go_left.astype(jnp.float32), precision=hp)
        hit = (counts == 0).astype(jnp.float32)
        outs[i % K] = outs[i % K] + jnp.dot(jnp.asarray(lv), hit,
                                            precision=hp)
    return np.asarray(jnp.stack(outs))


def _trained_case(name):
    """(trees, K, rows to score) of one trained case."""
    rng = np.random.RandomState(len(name))
    N, F, rounds = 3000, 10, 4
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 2}
    kw = {}
    if name == "features6":
        F = 6
    elif name == "features130":
        F = 130
    X = rng.normal(size=(N, F)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] + rng.normal(scale=0.3, size=N)
         > 0).astype(np.float32)
    if name == "leaves255":
        params["num_leaves"] = 255
    elif name == "multiclass3":
        params.update(objective="multiclass", num_class=3)
        y = (y + (X[:, 3] > 0.5)).astype(np.float32)
    elif name == "missing_nan":
        X[rng.rand(N, F) < 0.1] = np.nan
    elif name == "missing_zero":
        X[rng.rand(N, F) < 0.2] = 0.0
        params["zero_as_missing"] = True
    elif name == "categorical":
        X[:, 0] = rng.randint(0, 40, size=N)
        y = ((X[:, 0] % 3 == 0) ^ (X[:, 1] > 0)).astype(np.float32)
        kw["categorical_feature"] = [0]
    bst = lgb.train(params, lgb.Dataset(X, label=y, **kw),
                    num_boost_round=rounds)
    rows = 5000 if name == "ragged_rows" else 700
    Xs = rng.normal(size=(rows, F)).astype(np.float32)
    m = min(rows, N)                    # half the values are training's
    Xs[:m] = np.where(rng.rand(m, F) < 0.5, X[:m], Xs[:m])
    if name == "categorical":
        Xs[:, 0] = rng.randint(-2, 70, size=rows)
        Xs[::13, 0] = np.nan
    if name == "missing_nan":
        Xs[rng.rand(rows, F) < 0.1] = np.nan
    if name == "missing_zero":
        Xs[rng.rand(rows, F) < 0.2] = 0.0
        Xs[::7, 1] = np.nan
    g = bst._gbdt
    return g.models, g.num_tree_per_iteration, Xs


def _hand_tree(rng, nodes, leaf_values):
    """A tree of len(nodes) + 1 leaves grown as LightGBM numbers one (node
    i splits a seeded leaf of the i + 1 there are; the right child is
    leaf i + 1). A node is (feature, threshold, decision_type), its
    threshold a list of bitset words where it is categorical."""
    from lightgbm_tpu.models.tree import Tree
    t = Tree(len(nodes) + 1)
    link = {0: None}                     # leaf -> (parent node, is_left)
    words, bounds = [], [0]
    for i, (f, thr, dtype) in enumerate(nodes):
        leaf = rng.randint(0, i + 1)
        if link[leaf] is not None:
            node, is_left = link[leaf]
            (t.left_child if is_left else t.right_child)[node] = i
        t.left_child[i], t.right_child[i] = ~leaf, ~(i + 1)
        link[leaf], link[i + 1] = (i, True), (i, False)
        t.split_feature[i], t.decision_type[i] = f, dtype
        if dtype & 1:
            t.threshold[i] = t.threshold_in_bin[i] = len(bounds) - 1
            words += list(thr)
            bounds.append(len(words))
        else:
            t.threshold[i] = thr
    t.leaf_value[:] = leaf_values
    if len(bounds) > 1:
        t.num_cat = len(bounds) - 1
        t.cat_boundaries = np.asarray(bounds, np.int32)
        t.cat_threshold = np.asarray(words, np.uint32)
    return t


# a threshold's neighbours, the ends of the range and of the zero band
_F32 = np.finfo(np.float32)
_EDGE_THRESHOLDS = [-1.5, 0.0, -0.0, 0.1, 1e-40, -1e-40, 1e-35, -1e-35,
                    1e-36, float(_F32.max), -float(_F32.max), np.inf]


def _edge_values():
    """Float32 values at, one ulp under and one ulp over every edge
    threshold as the device sees it (floored), plus the infinities, both
    zeros, the smallest denormals and NaN."""
    from lightgbm_tpu.models.predictor import floor_threshold_f32
    at = floor_threshold_f32(np.asarray(_EDGE_THRESHOLDS))
    with np.errstate(over="ignore"):
        vals = np.concatenate([
            at, np.nextafter(at, np.float32(-np.inf)),
            np.nextafter(at, np.float32(np.inf)),
            np.asarray([np.nan, np.inf, -np.inf, 0.0, -0.0,
                        _F32.smallest_subnormal, -_F32.smallest_subnormal,
                        _F32.tiny, -_F32.tiny], np.float32)])
    return vals.astype(np.float32)


def _hand_case(name):
    """(trees, K, rows) of a forest built node by node. Leaf values are
    whole numbers whose sums float32 holds, so the device's float32
    margins must equal the host walk's float64 ones."""
    rng = np.random.RandomState(len(name))
    if name == "edge_values":
        # one node a tree, so every row meets every node: each of 3
        # features x every edge threshold x the 3 missing types x both
        # default directions; a class's margin is the bit mask of its 24
        # trees' decisions
        F, vals = 3, _edge_values()
        nodes = [(f, thr, missing << 2 | default_left << 1)
                 for f in range(F) for thr in _EDGE_THRESHOLDS
                 for missing in range(3) for default_left in range(2)]
        K = len(nodes) // 24
        trees = [_hand_tree(rng, [nd], [2.0 ** (i // K), 0.0])
                 for i, nd in enumerate(nodes)]
        return trees, K, np.concatenate([
            np.stack([vals[rng.permutation(len(vals))] for _ in range(F)], 1)
            for _ in range(8)])
    F, K = 5, 1
    X = rng.normal(size=(2500, F)).astype(np.float32)
    X[rng.rand(*X.shape) < 0.05] = np.nan
    X[rng.rand(*X.shape) < 0.05] = 0.0
    if name in ("thresholds300", "terms3_stub"):
        # 20 trees x 15 nodes, feature 0 at every node: 300 thresholds,
        # each one a row's own value, so a rank off by one moves a row
        def node():
            return (0 if rng.rand() < 0.9 else rng.randint(1, F),
                    float(np.nan_to_num(X[rng.randint(len(X)),
                                          rng.randint(F)])),
                    rng.randint(0, 3) << 2 | rng.randint(0, 2) << 1)
        trees = [_hand_tree(rng, [node() for _ in range(15)],
                            rng.randint(0, 1 << 16, 16)) for _ in range(20)]
        for t in trees:
            own = t.split_feature == 0
            t.threshold[own] = np.nan_to_num(
                X[rng.randint(0, len(X), own.sum()), 0])
        return trees, K, X
    assert name == "numeric_and_categorical"
    # feature 0 holds categories and is also compared as a number;
    # feature 1 is categorical alone, with two-word sets
    X[:, 0] = rng.randint(-3, 40, len(X))
    X[:, 1] = rng.randint(0, 70, len(X))
    X[::17, :2] = np.nan
    X[5::17, :2] += 0.5

    def node():
        f = rng.randint(0, 4)
        if f == 1 or (f == 0 and rng.rand() < 0.5):
            return (f, [rng.randint(0, 1 << 32, dtype=np.uint64)
                        for _ in range(1 + f)], 1)
        return (f, float(np.nan_to_num(X[rng.randint(len(X)), f])),
                rng.randint(0, 3) << 2 | rng.randint(0, 2) << 1)
    trees = []
    for _ in range(12):
        nodes = [node() for _ in range(rng.randint(3, 31))]
        trees.append(_hand_tree(rng, nodes,
                                rng.randint(0, 1 << 16, len(nodes) + 1)))
    return trees, 2, X


TRAINED = ["leaves255", "leaves15", "multiclass3", "missing_nan",
           "missing_zero", "categorical", "ragged_rows", "features6",
           "features130"]
HAND = ["edge_values", "thresholds300", "terms3_stub",
        "numeric_and_categorical"]


@pytest.mark.parametrize("name", TRAINED + HAND)
def test_fused_kernel_is_bit_equal(monkeypatch, name):
    """The one-kernel predictor under the Pallas interpreter and the XLA
    scans built from the same per-tree function give the bits of the
    host walk: the float32 reference walk, the removed three-stage body
    and, where the leaf values are whole numbers, PackedModel's own
    float64 margins."""
    from lightgbm_tpu.models import predictor
    from lightgbm_tpu.models.tree import MISSING_NAN, MISSING_ZERO
    from lightgbm_tpu.runtime import profiler
    trees, K, X = (_hand_case if name in HAND else _trained_case)(name)
    has_cat = any(t.num_cat > 0 for t in trees)
    if name == "terms3_stub":
        # three digits a code, and the thresholds counted 64 at a time
        monkeypatch.setattr(predictor, "_code_terms", lambda codes_max: 3)
        monkeypatch.setattr(predictor, "_COUNT_CHUNK", 64)
    tables = predictor.build_device_tables(trees, K, X.shape[1])
    assert tables.tkeys.shape[1] % (64 if name == "terms3_stub" else 8) == 0
    kinds = {(int(d) >> 2) & 3 for t in trees for d in t.decision_type}
    assert tables.has_zero == (MISSING_ZERO in kinds)
    assert tables.has_nan == (MISSING_NAN in kinds or has_cat)
    assert (tables.ncat is not None) == has_cat == (
        name in ("categorical", "numeric_and_categorical"))
    assert tables.ohf.dtype == np.int8
    assert tables.ohf.shape[2] == tables.F_pad == tables.tkeys.shape[0]
    # the term count is read from the forest: one int8 digit up to 126
    # thresholds or categories a code row, two up to 2 ** 14 - 2
    assert tables.terms == {"thresholds300": 2, "terms3_stub": 3}.get(
        name, 1)
    assert (126 < tables.thresholds_max <= 300) == (
        name in ("thresholds300", "terms3_stub"))
    assert tables.dual == ((0,) if name == "numeric_and_categorical"
                           else ())

    def margins(interpret):
        monkeypatch.setenv("LIGHTGBM_TPU_PALLAS_INTERPRET",
                           "1" if interpret else "0")
        with profiler.span("t/fused"):
            out = predictor.predict_margin_device(trees, K, X,
                                                  tables=tables)
        counts = [r for r in profiler.spans()
                  if r["name"] == "predict/dispatch"][-1]["counts"]
        assert counts["fused"] == int(interpret)
        assert X.shape[0] % counts["row_tile"] != 0
        assert counts["code_terms"] == tables.terms
        assert counts["thresholds_max"] == tables.thresholds_max
        return out

    kernel = margins(True)
    assert np.array_equal(kernel, margins(False))
    assert np.array_equal(kernel.astype(np.float32), _walk_f32(trees, K, X))
    if name in HAND:
        walk = predictor.PackedModel(trees, K).predict_margin(
            X.astype(np.float64))
        assert np.array_equal(kernel, walk)
    else:
        assert np.array_equal(kernel.astype(np.float32),
                              _removed_xla_body(trees, K, X))


# ---------------------------------------------------------------------------
# the pass as a pipeline over row blocks (predictor._row_blocks)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,features,row_tile,want", [
    # the Bosch and the Higgs tables: 245 tiles as 19 blocks of 13 (two
    # tiles scored twice), 2,564 tiles as 5 blocks of 513 (one)
    (1_000_000, 968, 4096, (13 * 4096, 19, 3)),
    (10_500_000, 28, 4096, (513 * 4096, 5, 3)),
    # up to 256 MiB: one block, the table itself, padded by _layout
    (100_000, 28, 4096, (100_000, 1, 1)),
    (65_000, 968, 4096, (65_000, 1, 1)),
    (0, 28, 4096, (0, 1, 1)),
    # just over: two blocks, fewer in flight than the line allows
    (70_000, 968, 4096, (9 * 4096, 2, 2)),
    # a row wider than a block's bytes: a tile a block
    (20_000, 40_000, 1024, (1024, 20, 3)),
])
def test_row_block_rule(rows, features, row_tile, want):
    from lightgbm_tpu.models import predictor
    got = predictor._row_blocks(rows, features, row_tile)
    assert got == want
    block_rows, blocks, in_flight = got
    if blocks > 1:
        # one shape, whole tiles, the last block inside the table
        assert block_rows % row_tile == 0 and block_rows < rows
        assert (blocks - 1) * block_rows < rows <= blocks * block_rows
        assert block_rows * features * 4 <= 256 << 20
        twice = blocks * block_rows - -(-rows // row_tile) * row_tile
        assert 0 <= twice <= 0.02 * rows


@pytest.mark.parametrize("name", ["ragged_rows", "multiclass3",
                                  "categorical"])
def test_row_blocks_are_bit_equal_to_one_block(monkeypatch, force_row_blocks,
                                               name):
    """A call of three blocks, the last of which overlaps the second,
    gives the bits of the one-block call and of the host walk: dense
    rows, K = 3, a categorical forest with NaN rows (the Bosch-shaped
    NaN table: tests/test_predictor_missing.py)."""
    from lightgbm_tpu.models import predictor
    from lightgbm_tpu.runtime import profiler
    trees, K, X = _trained_case(name)
    X = X[np.random.RandomState(5).randint(0, len(X), 9000)]
    tables = predictor.build_device_tables(trees, K, X.shape[1])
    assert tables.row_tile == 4096 and len(X) % 4096 != 0
    monkeypatch.setenv("LIGHTGBM_TPU_PALLAS_INTERPRET", "1")

    def margins():
        with profiler.span("t/blocks"):
            out = predictor.predict_margin_device(trees, K, X, tables=tables)
        return out, [r for r in profiler.spans()
                     if r["name"] == "t/blocks"][-1]["counts"]

    one, counts = margins()
    assert counts == {"blocks": 1, "block_rows": 9000, "in_flight": 1}
    force_row_blocks()
    many, counts = margins()
    assert counts == {"blocks": 3, "block_rows": 4096, "in_flight": 2}
    assert many.dtype == np.float64 and many.shape == (K, 9000)
    assert np.array_equal(one, many)
    assert np.array_equal(many.astype(np.float32), _walk_f32(trees, K, X))
    # a device-resident table is cut on the device, to the same bits
    import jax.numpy as jnp
    assert np.array_equal(many, predictor.predict_margin_device(
        trees, K, jnp.asarray(X), tables=tables))


@pytest.mark.parametrize("in_flight", [1, 2, 3, 5])
def test_rows_two_blocks_score_are_written_once(monkeypatch, force_row_blocks,
                                                in_flight):
    """Each row keeps the margin of the first block that holds it: with
    a body that answers a block's number, rows 4,904 to 8,191 of 9,000
    (in the second block and again in the third, which starts at 9,000 -
    4,096) read 1, whatever the number of blocks in flight."""
    import jax.numpy as jnp
    from lightgbm_tpu.models import predictor
    trees, K, X = _hand_case("thresholds300")
    X = np.concatenate([X] * 4)[:9000]
    tables = predictor.build_device_tables(trees, K, X.shape[1])
    force_row_blocks(in_flight=in_flight)
    seen = []

    def body(codes, *tabs, **static):
        seen.append(codes.shape)
        return jnp.full((K, codes.shape[1]), len(seen) - 1, jnp.float32)

    monkeypatch.setattr(predictor, "_get_device_margin", lambda: body)
    out = predictor.predict_margin_device(trees, K, X, tables=tables)
    assert seen == [(tables.terms * tables.F_pad, 4096)] * 3
    assert np.array_equal(out[0], np.arange(9000) // 4096)
