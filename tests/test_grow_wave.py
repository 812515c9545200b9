"""Wave grower correctness: parity with the serial leaf-wise growers.

The wave grower applies the same split mathematics as the serial paths;
with waves of K=1 it IS leaf-wise. These tests check (a) tree validity and
training quality against the compact serial grower on the same data,
(b) exact structural parity in regimes where wave order provably matches
leaf-wise order, (c) constraints (num_leaves / max_depth / min_data) hold.
"""

import numpy as np
import pytest
from sklearn.datasets import make_classification, make_regression
from sklearn.metrics import roc_auc_score

import lightgbm_tpu as lgb


def _train(X, y, grower, **over):
    params = dict(objective="binary", num_leaves=31, learning_rate=0.2,
                  min_data_in_leaf=5, verbose=-1, tpu_grower=grower)
    params.update(over)
    ds = lgb.Dataset(X, label=y)
    return lgb.train(params, ds, num_boost_round=8)


@pytest.fixture(scope="module")
def data():
    X, y = make_classification(n_samples=2000, n_features=12,
                               n_informative=8, random_state=7)
    return X.astype(np.float32), y.astype(np.float32)


def test_wave_matches_serial_quality(data):
    X, y = data
    auc_wave = roc_auc_score(y, _train(X, y, "wave").predict(X))
    auc_serial = roc_auc_score(y, _train(X, y, "compact").predict(X))
    assert auc_wave > 0.97
    assert abs(auc_wave - auc_serial) < 0.01


def test_wave_exact_trees_identical_to_serial(data):
    """wave_exact reorders device work, NOT the algorithm: trees must
    equal the serial leaf-wise grower's split for split. (The wave path
    synthesizes per-bin counts from hessians — the reference's cnt_factor
    approximation — so min_data_in_leaf is kept tiny here and exact
    leaf_count metadata is not compared.)

    The two growers fuse the same float math differently (the wave path
    derives sibling histograms by parent-minus-smaller subtraction), so
    leaf values carry last-bit drift that compounds over boosting rounds
    — they are compared with a float tolerance, not by decimal rounding
    (round-then-compare fails on values straddling a rounding boundary,
    e.g. -0.06815 vs -0.0681499). Structure must still be identical; a
    structural divergence must be a certified float-noise gain tie
    (docs/PARITY.md §Cross-grower near-tie stability)."""
    X, y = data
    mw = _train(X, y, "wave_exact",
                min_data_in_leaf=2).dump_model()["tree_info"]
    ms = _train(X, y, "compact",
                min_data_in_leaf=2).dump_model()["tree_info"]
    assert len(mw) == len(ms)

    def flat(node, splits, leaves):
        if "leaf_index" in node:
            leaves.append(node["leaf_value"])
        else:
            splits.append((node["split_feature"], node["threshold"],
                           node.get("split_gain", 0.0)))
            flat(node["left_child"], splits, leaves)
            flat(node["right_child"], splits, leaves)

    for tw, ts in zip(mw, ms):
        sw, lw = [], []
        ss, ls = [], []
        flat(tw["tree_structure"], sw, lw)
        flat(ts["tree_structure"], ss, ls)
        struct_w = [(f, round(t, 6)) for f, t, _ in sw]
        struct_s = [(f, round(t, 6)) for f, t, _ in ss]
        if struct_w != struct_s:
            # first structural divergence must be a float-noise gain tie
            i = next(j for j, (a, b) in enumerate(zip(struct_w, struct_s))
                     if a != b)
            np.testing.assert_allclose(
                sw[i][2], ss[i][2], rtol=1e-4, atol=1e-6,
                err_msg=f"structural divergence at split {i} "
                        "is not a near-tie")
            break  # cascade: later nodes/trees legitimately differ
        np.testing.assert_allclose(lw, ls, rtol=1e-3, atol=2e-4)


def test_wave_single_split_exact(data):
    """num_leaves=2: one split — wave and serial must agree exactly."""
    X, y = data
    bw = _train(X, y, "wave", num_leaves=2)
    bs = _train(X, y, "compact", num_leaves=2)
    np.testing.assert_allclose(bw.predict(X), bs.predict(X),
                               rtol=1e-5, atol=1e-6)


def test_wave_respects_limits(data):
    X, y = data
    b = _train(X, y, "wave", num_leaves=17, max_depth=4)
    m = b.dump_model()
    for tree in m["tree_info"]:
        leaves = tree["num_leaves"]
        assert leaves <= 17

        def depth(node, d=0):
            if "leaf_index" in node:
                return d
            return max(depth(node["left_child"], d + 1),
                       depth(node["right_child"], d + 1))
        assert depth(tree["tree_structure"]) <= 4


def test_wave_min_data(data):
    X, y = data
    b = _train(X, y, "wave", min_data_in_leaf=50)
    m = b.dump_model()

    def walk(node):
        if "leaf_index" in node:
            assert node["leaf_count"] >= 50
        else:
            walk(node["left_child"])
            walk(node["right_child"])
    for tree in m["tree_info"]:
        walk(tree["tree_structure"])


def test_wave_regression():
    X, y = make_regression(n_samples=1500, n_features=10, noise=4.0,
                           random_state=3)
    ds = lgb.Dataset(X.astype(np.float32), label=y.astype(np.float32))
    b = lgb.train(dict(objective="regression", num_leaves=31, verbose=-1,
                       tpu_grower="wave", learning_rate=0.2), ds,
                  num_boost_round=10)
    pred = b.predict(X)
    mse0 = float(np.mean((y - y.mean()) ** 2))
    mse = float(np.mean((y - pred) ** 2))
    assert mse < 0.25 * mse0


def test_wave_with_nans_and_bagging(data):
    X, y = data
    Xn = X.copy()
    Xn[::5, 2] = np.nan
    b = _train(Xn, y, "wave", bagging_fraction=0.7, bagging_freq=1,
               feature_fraction=0.8)
    auc = roc_auc_score(y, b.predict(Xn))
    assert auc > 0.95


def test_wave_save_load_roundtrip(data, tmp_path):
    X, y = data
    b = _train(X, y, "wave")
    p = tmp_path / "m.txt"
    b.save_model(str(p))
    b2 = lgb.Booster(model_file=str(p))
    np.testing.assert_allclose(b.predict(X), b2.predict(X), rtol=1e-6)


def test_lambdarank_device_matches_host_gradients():
    """The device (bucketed) lambdarank path must reproduce the host
    per-query reference implementation."""
    import jax.numpy as jnp
    from lightgbm_tpu.config import resolve_params
    from lightgbm_tpu.objectives.rank import LambdarankNDCG

    rng = np.random.RandomState(3)
    sizes = [7, 12, 3, 30, 1, 18]
    N = sum(sizes)
    labels = np.concatenate([
        rng.randint(0, 4, size=s) for s in sizes]).astype(np.float32)
    qb = np.concatenate([[0], np.cumsum(sizes)])

    class MD:
        label = labels
        weight = None
        query_boundaries = qb

    cfg = resolve_params({"objective": "lambdarank"})
    obj = LambdarankNDCG(cfg)
    obj.init(MD, N)
    score = rng.normal(size=N).astype(np.float32)
    gd, hd = obj.get_gradients(jnp.asarray(score), None, None)
    gh, hh = obj.get_gradients_numpy(score)
    np.testing.assert_allclose(np.asarray(gd), gh, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(hd), hh, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("route,F,root_rows", [
    ("mega", 8, 0), ("decide", 40, 0), ("mega", 8, 512)])
def test_categorical_tree_on_the_kernels_equals_portable(route, F, root_rows,
                                                         monkeypatch):
    """A whole categorical tree grown on the megakernel (8 columns) or on
    the in-kernel decision (40), both under the Pallas interpreter, is
    the portable route's tree: every split's feature, threshold or
    category set, every count and every leaf value (quantized gradients:
    integer histograms on both routes). ``root_rows`` cuts the root
    histogram into row partitions of that many rows (ROOT_PART_ROWS),
    summed apart and then together: the same tree."""
    from lightgbm_tpu.ops import grow_wave
    if root_rows:
        monkeypatch.setattr(grow_wave, "ROOT_PART_ROWS", root_rows)
    rng = np.random.RandomState(11)
    n = 1500
    X = rng.normal(size=(n, F)).astype(np.float32)
    X[:, 0] = rng.randint(0, 40, size=n)
    X[:, 2] = rng.randint(0, 6, size=n)
    eff = rng.normal(size=40)
    y = (eff[X[:, 0].astype(int)] + X[:, 1] + 0.3 * X[:, 2]
         > 0.2).astype(np.float32)
    params = dict(objective="binary", num_leaves=15, verbose=-1,
                  min_data_in_leaf=5, max_bin=63, use_quantized_grad=True,
                  min_data_per_group=5, cat_smooth=1.0,
                  histogram_impl="auto", binning_impl="host",
                  deterministic=True)
    routes = []
    real = grow_wave.wave_route
    monkeypatch.setattr(grow_wave, "wave_route",
                        lambda X_t, cfg: routes.append(real(X_t, cfg))
                        or routes[-1])

    def grow():
        ds = lgb.Dataset(X, label=y, categorical_feature=[0, 2])
        return lgb.train(params, ds, num_boost_round=2).dump_model()[
            "tree_info"]

    monkeypatch.setenv("LIGHTGBM_TPU_PALLAS_INTERPRET", "1")
    kernels = grow()
    assert routes and set(routes) == {route}, routes
    monkeypatch.delenv("LIGHTGBM_TPU_PALLAS_INTERPRET")
    routes.clear()
    portable = grow()
    assert set(routes) == {"portable"}, routes
    assert any(t["num_cat"] > 0 for t in portable)
    assert kernels == portable
