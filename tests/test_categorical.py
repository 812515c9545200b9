"""Categorical split tests (reference behavior: test_engine.py categorical
cases — one-hot and sorted many-vs-many splits, save/load round-trip)."""

import numpy as np

import lightgbm_tpu as lgb


def _cat_problem(n=2000, seed=3, num_cats=12):
    """Label depends on membership of a category subset — only a many-vs-many
    categorical split can separate it well."""
    rng = np.random.RandomState(seed)
    cat = rng.randint(0, num_cats, size=n)
    x_num = rng.normal(size=n)
    good = {1, 3, 4, 8, 11}
    y = (np.isin(cat, list(good)) ^ (x_num > 1.5)).astype(np.float32)
    X = np.column_stack([cat.astype(np.float64), x_num])
    return X, y


def test_categorical_split_learns_subset():
    X, y = _cat_problem()
    ds = lgb.Dataset(X, label=y, categorical_feature=[0])
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "min_data_in_leaf": 20, "verbose": -1,
                     "min_data_per_group": 10},
                    ds, num_boost_round=20)
    pred = bst.predict(X)
    acc = np.mean((pred > 0.5) == (y > 0.5))
    assert acc > 0.9, acc
    # at least one tree must actually contain a categorical split
    assert any(t.num_cat > 0 for t in bst._gbdt.models)


def test_categorical_save_load_roundtrip():
    X, y = _cat_problem(n=1200, seed=9)
    ds = lgb.Dataset(X, label=y, categorical_feature=[0])
    bst = lgb.train({"objective": "binary", "num_leaves": 8, "verbose": -1,
                     "min_data_per_group": 10},
                    ds, num_boost_round=8)
    p1 = bst.predict(X)
    bst2 = lgb.Booster(model_str=bst.model_to_string())
    p2 = bst2.predict(X)
    np.testing.assert_allclose(p1, p2, rtol=1e-6, atol=1e-7)


def test_onehot_categorical_small_cardinality():
    # num_bins <= max_cat_to_onehot triggers the one-hot path
    rng = np.random.RandomState(1)
    n = 800
    cat = rng.randint(0, 3, size=n)
    y = (cat == 2).astype(np.float32)
    X = np.column_stack([cat.astype(np.float64), rng.normal(size=n)])
    ds = lgb.Dataset(X, label=y, categorical_feature=[0])
    bst = lgb.train({"objective": "binary", "num_leaves": 4, "verbose": -1,
                     "max_cat_to_onehot": 4, "min_data_in_leaf": 5},
                    ds, num_boost_round=10)
    pred = bst.predict(X)
    assert np.mean((pred > 0.5) == (y > 0.5)) > 0.99


def test_unseen_category_goes_right():
    X, y = _cat_problem(n=1000, seed=5, num_cats=6)
    ds = lgb.Dataset(X, label=y, categorical_feature=[0])
    bst = lgb.train({"objective": "binary", "num_leaves": 8, "verbose": -1,
                     "min_data_per_group": 10},
                    ds, num_boost_round=5)
    X_unseen = X.copy()
    X_unseen[:5, 0] = 99  # category never seen in training
    out = bst.predict(X_unseen)
    assert np.all(np.isfinite(out[:5]))


# ---- the search against a plain-Python port of the reference's ---------
def _inner(g, h, count, G, H, num_bin, prm, stepping=True):
    """FindBestThresholdCategoricalInner (feature_histogram.cpp), sorted
    many-vs-many mode, line by line in float64: the best gain over the
    parent and its left set of bins (bins 1..num_bin-1 are categories).
    ``stepping=False`` evaluates every prefix (the rule before the
    stepping was kept), for the cases that tell the two apart."""
    eps = 1e-15
    l2, smooth = prm["lambda_l2"], prm["cat_smooth"]
    cf = count / H
    cnt = np.rint(h * cf)
    parent = G * G / (H + l2)
    idx = [t for t in range(1, num_bin) if cnt[t] >= smooth]
    idx.sort(key=lambda t: g[t] / (h[t] + smooth))
    used = len(idx)
    l2 += prm["cat_l2"]
    max_num_cat = min(prm["max_cat_threshold"], (used + 1) // 2)
    best, best_set = -np.inf, None
    for order in (idx, idx[::-1]):
        lg, lh, lc, group = 0.0, eps, 0.0, 0.0
        for i in range(min(used, max_num_cat)):
            t = order[i]
            lg, lh, lc, group = lg + g[t], lh + h[t], lc + cnt[t], \
                group + cnt[t]
            if lc < prm["min_data_in_leaf"] \
                    or lh < prm["min_sum_hessian_in_leaf"]:
                continue
            rc = count - lc
            if rc < prm["min_data_in_leaf"] \
                    or rc < prm["min_data_per_group"]:
                break
            rh = H - lh
            if rh < prm["min_sum_hessian_in_leaf"]:
                break
            if stepping and group < prm["min_data_per_group"]:
                continue
            group = 0.0
            rg = G - lg
            gain = lg * lg / (lh + l2) + rg * rg / (rh + l2)
            if gain > parent and gain > best:
                best, best_set = gain, sorted(order[:i + 1])
    return best - parent, best_set


def _program_search(g, h, count, G, H, num_bin, prm):
    import jax.numpy as jnp
    from lightgbm_tpu.ops.categorical import (CatConfig,
                                              find_best_split_categorical)
    from lightgbm_tpu.ops.split import FeatureMeta, SplitHyperParams
    B = len(g)
    c = h * (count / H)                 # the search rounds it per bin
    hist = jnp.asarray(np.stack([g, h, c])[:, None, :], jnp.float32)
    meta = FeatureMeta(jnp.array([num_bin], jnp.int32),
                       jnp.array([0], jnp.int32), jnp.array([0], jnp.int32),
                       jnp.array([True]))
    hp = SplitHyperParams(prm["min_data_in_leaf"],
                          prm["min_sum_hessian_in_leaf"], 0.0,
                          prm["lambda_l2"], 0.0, 0.0, 0.0)
    cat = CatConfig(prm["max_cat_to_onehot"], prm["max_cat_threshold"],
                    prm["cat_l2"], prm["cat_smooth"],
                    prm["min_data_per_group"], (B + 31) // 32)
    res, words = find_best_split_categorical(
        hist, jnp.float32(G), jnp.float32(H), jnp.int32(count),
        jnp.float32(0.0), meta, hp, cat)
    w = np.asarray(words)
    return float(res.gain), [b for b in range(B)
                             if (int(w[b >> 5]) >> (b & 31)) & 1]


def test_stepping_rule_matches_the_reference_search():
    """Seeded histograms of 20-200 categories whose bins hold 5-400 rows,
    so min_data_per_group (100) binds: the program's search picks the
    reference's left set on every one, and the old rule (every prefix
    evaluated) picks another on some of them."""
    prm = dict(lambda_l2=0.0, cat_smooth=10.0, cat_l2=10.0,
               max_cat_threshold=32, max_cat_to_onehot=4,
               min_data_per_group=100, min_data_in_leaf=1,
               min_sum_hessian_in_leaf=1.0)
    told_apart = 0
    for seed in range(24):
        rng = np.random.RandomState(seed)
        B = 64 if seed % 2 else 256
        num_bin = rng.randint(20, B - 1)
        rows = rng.randint(5, 400, size=B).astype(np.float64)
        rows[num_bin:] = 0
        rows[0] = rng.randint(0, 50)
        h = rows * rng.uniform(0.1, 0.25, size=B)
        g = h * rng.normal(0.0, 1.0, size=B)
        count, G, H = float(rows.sum()), float(g.sum()), float(h.sum())
        want_gain, want = _inner(g, h, count, G, H, num_bin, prm)
        gain, got = _program_search(g, h, count, G, H, num_bin, prm)
        assert got == want, (seed, got, want)
        assert abs(gain - want_gain) <= 1e-4 * abs(want_gain), seed
        told_apart += _inner(g, h, count, G, H, num_bin, prm,
                             stepping=False)[1] != want
    assert told_apart >= 3, told_apart
