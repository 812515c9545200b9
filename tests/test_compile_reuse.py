"""A new dataset of a known shape reuses the compiled training programs.

The persistent compile cache keys on the lowered text. So no jitted
program of the training path may hold an array derived from the data as
a constant: the dataset's per-feature facts (`FeatureMeta`: bin counts,
the bin that holds 0.0, missing types) and the binning table are
ARGUMENTS (models/gbdt.py `_build_jit_fns`, docs/PERF.md §7). Held here
on the CPU, by lowering only (nothing compiles, nothing runs): two seeds
of one shape and one parameter set lower to the same text, for every
entry a trainer can take; and what IS static stays static: a forest with
monotone constraints lowers to another program than one without.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb

ROWS, F = 3000, 8
PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
          "min_data_in_leaf": 5, "verbosity": -1}


def _table(seed):
    """A standard-normal table: the bin that holds 0.0 (`default_bin`)
    moves from feature to feature with the seed (asserted below, or the
    test would compare a dataset with itself)."""
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(ROWS, F)).astype(np.float32)
    y = (X[:, 0] + 0.5 * rng.normal(size=ROWS) > 0).astype(np.float64)
    return X, y


def _gbdt(seed, **extra):
    X, y = _table(seed)
    p = dict(PARAMS, **extra)
    return lgb.Booster(params=p,
                       train_set=lgb.Dataset(X, label=y, params=p))._gbdt


def _tree_args(g):
    n = g.scores.shape[1]
    z = jnp.zeros((n,), jnp.float32)
    return (g.X_t, z, z, jnp.ones((n,), jnp.float32), g.scores[0],
            jnp.float32(0.1), jnp.ones((len(g.mappers),), bool),
            jnp.int32(0), g.meta)


def _lower_scan(g):
    n_pad = 2
    return g._get_scan_fn(n_pad, g._batched_sampling_mode()).lower(
        g.X_t, g.scores, g.label_dev, g.weight_dev,
        jnp.ones((g._host_pad,), jnp.float32), jnp.float32(0.1),
        jnp.int32(0), jnp.int32(n_pad),
        jnp.ones((n_pad, len(g.mappers)), bool), g.meta,
        (), (), (), (), (), ())


def _lower_tree(g):
    if g.use_dist:
        return g._train_tree.lower(*_tree_args(g))
    return g._train_tree_core.lower(*_tree_args(g), g._cegb_used)


def _lower_bucketize(g):
    from lightgbm_tpu.ops import bucketize as bz
    t = bz.pack_bin_table(g.mappers, mode="train")
    Xc = jnp.zeros((256, t.num_features), jnp.float32)
    return bz._bin_rows_jit().lower(Xc, t.table, t.cat_val, t.meta)


CASES = {
    "scan-wave": ({"tpu_grower": "wave"}, _lower_scan),
    "scan-compact": ({"tpu_grower": "compact"}, _lower_scan),
    "scan-default": ({}, _lower_scan),
    "tree-wave": ({"tpu_grower": "wave"}, _lower_tree),
    "tree-masked": ({"tpu_grower": "masked"}, _lower_tree),
    "tree-data-parallel": ({"tree_learner": "data"}, _lower_tree),
    "tree-feature-parallel": ({"tree_learner": "feature"}, _lower_tree),
    "bucketize": ({}, _lower_bucketize),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_two_seeds_lower_to_one_program(case):
    extra, lower = CASES[case]
    a, b = _gbdt(11, **extra), _gbdt(12, **extra)
    assert not np.array_equal(np.asarray(a.meta.default_bin),
                              np.asarray(b.meta.default_bin))
    assert a.grower == b.grower and a.grow_cfg == b.grow_cfg
    if "parallel" in case:               # conftest's 8 virtual devices
        assert a.use_dist and a.n_shards == 8
    ta, tb = lower(a).as_text(), lower(b).as_text()
    if ta != tb:
        diff = [(x, y) for x, y in zip(ta.splitlines(), tb.splitlines())
                if x != y]
        pytest.fail(f"{case}: {len(diff)} lines differ between two seeds; "
                    f"the first:\n- {diff[0][0][:300]}\n+ {diff[0][1][:300]}")


def test_structure_of_meta_is_still_static():
    """`None` fields of FeatureMeta are the tree's structure, not its
    leaves: a monotone forest compiles its own program."""
    plain = _gbdt(11, tpu_grower="wave")
    mono = _gbdt(11, tpu_grower="wave",
                 monotone_constraints=[1] + [0] * (F - 1))
    assert plain.meta.monotone is None and mono.meta.monotone is not None
    assert jax.tree.structure(plain.meta) != jax.tree.structure(mono.meta)
    assert _lower_scan(plain).as_text() != _lower_scan(mono).as_text()


def _rank_gbdt(seed):
    """A ranking job with a held-out set: the same multiset of query
    lengths in another order, other rows, other labels."""
    rng = np.random.RandomState(seed)
    p = {"objective": "lambdarank", "metric": "ndcg", "eval_at": [1, 3, 5],
         "num_leaves": 15, "max_bin": 63, "min_data_in_leaf": 5,
         "verbosity": -1}

    def part(lengths):
        ln = rng.permutation(lengths)
        X = rng.normal(size=(int(ln.sum()), F)).astype(np.float32)
        y = rng.randint(0, 5, size=len(X)).astype(np.float64)
        return X, y, ln

    X, y, ln = part(np.r_[np.arange(1, 40), [70, 150, 300]])
    ds = lgb.Dataset(X, label=y, group=ln, params=p)
    bst = lgb.Booster(params=p, train_set=ds)
    Xv, yv, lv = part(np.r_[np.arange(1, 25), [90]])
    bst.add_valid(lgb.Dataset(Xv, label=yv, group=lv, reference=ds), "v")
    return bst._gbdt


def test_two_ranking_datasets_lower_to_one_scan():
    """The objective's query buckets and the NDCG metric's reach the scan
    as arguments (`device_state()`), so a second ranking dataset of the
    same shape loads the compiled scan from the cache."""
    def lower(g):
        n_pad = 2
        lay = g._device_metric_layout()
        assert lay and g.objective.device_state() is not None
        return g._get_scan_fn(n_pad, g._batched_sampling_mode()).lower(
            g.X_t, g.scores, g.label_dev, g.weight_dev,
            jnp.ones((g._host_pad,), jnp.float32), jnp.float32(0.1),
            jnp.int32(0), jnp.int32(n_pad),
            jnp.ones((n_pad, len(g.mappers)), bool), g.meta,
            tuple(g._valid_Xt), tuple(tuple(m) for m in g._valid_meta),
            tuple(g._valid_scores), tuple(g._valid_label_dev),
            tuple(g._valid_weight_dev),
            tuple(jnp.float32(s) for s in g._valid_sumw),
            g.objective.device_state(),
            tuple(m.device_state() for _, m, _ in lay)).as_text()

    a, b = _rank_gbdt(21), _rank_gbdt(22)
    assert not np.array_equal(
        np.asarray(a.objective.device_state()["pos_of_row"]),
        np.asarray(b.objective.device_state()["pos_of_row"]))
    ta, tb = lower(a), lower(b)
    assert ta == tb, next((x, y) for x, y in
                          zip(ta.splitlines(), tb.splitlines()) if x != y)
