"""Learning to rank on the device: the query buckets, the LambdaRank
objective's bounded pair block, the device NDCG and the batched scan
that carries them (docs/PERF.md §7a), and the benchmark's plain
reference (bench/reference/rank_ref.py) held to the host path. Three
things that agree: the per-query NumPy loop, the device path, the
reference."""

import hashlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.metrics import NDCGMetric
from lightgbm_tpu.metrics import rank_buckets
from lightgbm_tpu.metrics.rank_utils import default_label_gain, eval_ndcg
from lightgbm_tpu.objectives.rank import LambdarankNDCG
from lightgbm_tpu.runtime import profiler

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
EVAL_AT = [1, 3, 5, 10]


@pytest.fixture(autouse=True)
def _batched(monkeypatch):
    """conftest turns the batched scan off for the suite's speed; these
    tests are about it."""
    monkeypatch.setenv("LIGHTGBM_TPU_DISABLE_BATCHED", "0")


class _Meta:
    def __init__(self, label, lengths, weight=None):
        self.label = np.asarray(label, np.float32)
        self.weight = weight
        self.query_boundaries = np.concatenate(
            [[0], np.cumsum(lengths)]).astype(np.int64)
        self.init_score = None


def _lengths(rng, nq, hi=60):
    ln = rng.integers(1, hi, size=nq)
    ln[0], ln[1], ln[2] = 1, 300, 2      # one document; the longest; a pair
    return ln


def _queries(seed, nq=40, kind="plain"):
    rng = np.random.default_rng(seed)
    ln = _lengths(rng, nq)
    n = int(ln.sum())
    y = rng.integers(0, 5, size=n).astype(np.float32)
    s = (0.5 * rng.standard_normal(n)).astype(np.float32)
    w = None
    if kind == "ties":
        s = np.round(s * 2) / 2          # a handful of distinct scores
        s[:400] = 0.0                    # whole queries tied, as at tree 1
    elif kind == "single":
        ln = np.ones(nq, np.int64)
        y, s = y[:nq], s[:nq]
    elif kind == "no_relevant":
        qb = np.concatenate([[0], np.cumsum(ln)])
        for q in range(0, nq, 3):
            y[qb[q]:qb[q + 1]] = 0.0
    elif kind == "weights":
        w = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    return ln, y, s, w


@pytest.mark.parametrize("kind", ["plain", "ties", "single", "no_relevant",
                                  "weights"])
def test_device_ndcg_matches_host(kind):
    ln, y, s, w = _queries(3, kind=kind)
    cfg = Config(eval_at=EVAL_AT)
    m = NDCGMetric(cfg)
    md = _Meta(y, ln, w)
    m.init(md, len(y))
    fn = m.device_eval_fn(None)
    assert m.result_names() == [f"ndcg@{k}" for k in EVAL_AT]
    got = np.asarray(jax.jit(fn)(
        jnp.asarray(s)[None, :], None, None, None, m.device_state()))
    want = [v for _, v, _ in eval_ndcg(
        s.astype(np.float64), md.label, md.query_boundaries, w, EVAL_AT,
        cfg.label_gain)]
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


def _old_buckets(qb, label, label_gain, imds, N):
    """The per-query loop the vectorised builder replaced (PR 37)."""
    lengths = np.diff(qb)
    buckets = {}
    for q, ln in enumerate(lengths):
        plen = 1 << max(3, int(np.ceil(np.log2(max(ln, 1)))))
        buckets.setdefault(plen, []).append(q)
    out, pos_of_row, offset = [], np.zeros(N, np.int64), 0
    for plen in sorted(buckets):
        qs = buckets[plen]
        idx = np.full((len(qs), plen), N, np.int64)
        lab = np.full((len(qs), plen), -1, np.int32)
        cnt = np.zeros(len(qs), np.int32)
        imd = np.zeros(len(qs), np.float32)
        for i, q in enumerate(qs):
            s, e = int(qb[q]), int(qb[q + 1])
            idx[i, :e - s] = np.arange(s, e)
            lab[i, :e - s] = label[s:e].astype(np.int32)
            cnt[i], imd[i] = e - s, imds[q]
            pos_of_row[s:e] = offset + i * plen + np.arange(e - s)
        out.append(dict(plen=plen, idx=idx, lab=lab, cnt=cnt, imd=imd))
        offset += len(qs) * plen
    return out, pos_of_row


def _old_inverse_max_dcgs(qb, label, label_gain, trunc):
    out = np.zeros(len(qb) - 1)
    for q in range(len(qb) - 1):
        top = np.sort(label[qb[q]:qb[q + 1]].astype(np.int64))[::-1][:trunc]
        mx = float(np.sum(label_gain[top]
                          / np.log2(np.arange(2, len(top) + 2))))
        out[q] = 1.0 / mx if mx > 0 else 0.0
    return out


def _objective(ln, y, block_bytes=None):
    obj = LambdarankNDCG(Config())
    if block_bytes is not None:
        obj.pair_block_bytes = block_bytes
    obj.init(_Meta(y, ln), len(y))
    return obj


def test_bucket_builder_matches_the_per_query_loop():
    ln, y, _, _ = _queries(5, nq=120)
    obj = _objective(ln, y)
    qb = obj.query_boundaries
    imds = _old_inverse_max_dcgs(qb, obj.label, obj.label_gain, 30)
    np.testing.assert_allclose(obj.inverse_max_dcgs, imds, rtol=1e-15)
    old, old_pos = _old_buckets(qb, obj.label, obj.label_gain, imds, len(y))
    state = obj.device_state()
    assert [b["plen"] for b in old] == [b["plen"] for b in obj._buckets]
    for o, new in zip(old, state["buckets"]):
        for key in ("idx", "lab", "cnt", "imd"):
            np.testing.assert_array_equal(np.asarray(new[key]), o[key], key)
    np.testing.assert_array_equal(np.asarray(state["pos_of_row"]), old_pos)


def test_device_lambdas_match_the_host_loop():
    ln, y, s, _ = _queries(7, nq=60, kind="ties")
    obj = _objective(ln, y)
    g, h = obj.get_gradients(jnp.asarray(s), None, None)
    gh, hh = obj.get_gradients_numpy(s.astype(np.float64))
    rms = float(np.sqrt(np.mean(gh ** 2)))
    assert np.max(np.abs(np.asarray(g) - gh)) < 1e-5 * rms
    assert np.max(np.abs(np.asarray(h) - hh)) < 1e-5 * rms


def test_blocked_pair_computation_is_bit_equal():
    """A budget of 64 KiB walks every bucket in blocks of a few queries;
    the lambdas are those of the whole buckets, bit for bit."""
    ln, y, s, _ = _queries(9, nq=150)
    whole, blocked = _objective(ln, y), _objective(ln, y, 64 << 10)
    assert all(b["idx"].ndim == 2 for b in whole.device_state()["buckets"])
    assert any(b["idx"].ndim == 3 for b in blocked.device_state()["buckets"])
    fn = lambda o: jax.jit(lambda s, st: o.get_gradients(s, None, None, st))(
        jnp.asarray(s), o.device_state())
    for a, b in zip(fn(whole), fn(blocked)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- the batched scan -------------------------------------------------
PARAMS = dict(objective="lambdarank", metric="ndcg", eval_at=EVAL_AT,
              num_leaves=15, min_data_in_leaf=5, max_bin=63, verbose=-1)


def _table(seed, nq):
    rng = np.random.default_rng(seed)
    ln = _lengths(rng, nq)
    n = int(ln.sum())
    X = rng.standard_normal((n, 10)).astype(np.float32)
    y = np.clip(1.2 * (X[:, 0] + 0.5 * rng.standard_normal(n)) + 1,
                0, 4).astype(np.int32).astype(np.float32)
    return X, y, ln


def _booster(valid=True, **extra):
    p = dict(PARAMS, **extra)
    X, y, ln = _table(1, 160)
    ds = lgb.Dataset(X, label=y, group=ln, params=p)
    bst = lgb.Booster(params=p, train_set=ds)
    if valid:
        Xv, yv, lv = _table(2, 60)
        bst.add_valid(lgb.Dataset(Xv, label=yv, group=lv, reference=ds),
                      "heldout")
    return bst


def test_can_batch_lambdarank_with_valid_ndcg():
    bst = _booster()
    assert bst._gbdt.can_batch_iters(4)
    assert bst.batched_eval_layout() == [
        ("heldout", f"ndcg@{k}", True) for k in EVAL_AT]


def test_cannot_batch_under_pre_partition():
    bst = _booster()
    bst._gbdt._pre_part = True           # valid replay is host-side there
    assert not bst._gbdt.can_batch_iters(4)


def test_batched_and_per_iteration_agree():
    a, b = _booster(), _booster()
    vals = np.concatenate([np.asarray(a.update_batch(4, chunk=4))
                           for _ in range(2)])
    assert a._gbdt.dispatch_count == 2           # one a chunk
    host = []
    for _ in range(8):
        b.update()
        host.append([v for _, _, v, _ in b.eval_valid()])
    md5 = lambda bst: hashlib.md5(bst.model_to_string().encode()).hexdigest()
    assert md5(a) == md5(b)
    np.testing.assert_allclose(vals, np.array(host), rtol=0, atol=1e-6)


def test_update_batch_returns_the_chunk_results():
    bst = _booster()
    vals = bst.update_batch(6, chunk=4)          # a chunk and a tail
    assert vals.shape == (6, len(bst.batched_eval_layout()))
    last = [v for _, _, v, _ in bst.eval_valid()]
    np.testing.assert_allclose(np.asarray(vals)[-1], last, atol=1e-6)
    assert _booster(valid=False).update_batch(4, chunk=4) is None


def test_scalar_device_metrics_keep_their_bits():
    """The metric stack is now a concatenation of results; a metric that
    gives one scalar reads what its own device function gives."""
    rng = np.random.default_rng(4)
    X = rng.standard_normal((3000, 8)).astype(np.float32)
    y = (X[:, 0] + 0.5 * rng.standard_normal(3000) > 0).astype(np.float32)
    p = dict(objective="binary", metric=["auc", "binary_logloss"],
             num_leaves=15, verbose=-1)
    ds = lgb.Dataset(X[:2000], label=y[:2000], params=p)
    bst = lgb.Booster(params=p, train_set=ds)
    bst.add_valid(lgb.Dataset(X[2000:], label=y[2000:], reference=ds), "v")
    vals = np.asarray(bst.update_batch(3, chunk=3))
    g = bst._gbdt
    assert [n for _, n, _ in g.batched_eval_layout()] \
        == ["auc", "binary_logloss"]
    direct = [np.asarray(jax.jit(fn)(
        g._valid_scores[vi], g._valid_label_dev[vi], g._valid_weight_dev[vi],
        jnp.float32(g._valid_sumw[vi])))
        for vi, _, fn in g._device_metric_layout()]
    np.testing.assert_array_equal(vals[-1], np.array(direct, np.float32))


def test_rank_spans_carry_their_counts():
    profiler.set_spans(True)
    bst = _booster()
    bst.update_batch(2, chunk=2)
    recs = profiler.spans()
    by_id = {r["id"]: r for r in recs}
    obj = [r for r in recs if r["name"] == "objective/init"][-1]
    met = [r for r in recs if r["name"] == "metric/init"][-1]
    chunk = [r for r in recs if r["name"] == "train/chunk"][-1]
    assert by_id[obj["root"]]["name"] == "booster/init"
    assert by_id[met["root"]]["name"] == "booster/add_valid"
    n = bst._gbdt.num_data
    assert obj["counts"]["rows"] == n and obj["counts"]["queries"] == 160
    assert obj["counts"]["padded_rows"] >= n and obj["counts"]["buckets"] >= 3
    assert obj["counts"]["pair_cells"] > 0
    assert met["counts"]["queries"] == 60
    assert met["counts"]["padded_rows"] >= met["counts"]["rows"]
    assert chunk["counts"]["metric_columns"] == 4
    assert chunk["counts"]["valid_rows"] == met["counts"]["rows"]


# ---- the benchmark's reference ----------------------------------------
@pytest.fixture(scope="module")
def rank_ref():
    for p in (BENCH,):
        if p not in sys.path:
            sys.path.insert(0, p)
    from reference import rank_ref as mod
    return mod


def test_reference_lambdas_match_one_query_loop(rank_ref):
    ln, y, s, _ = _queries(11, nq=60, kind="ties")
    obj = _objective(ln, y)
    rk = rank_ref.Ranking(ln, y, 30, EVAL_AT)
    for score in (np.zeros_like(s), s):
        lam, hes = rk.lambdas(jnp.asarray(score), {}, block=256)
        gh, hh = obj.get_gradients_numpy(score.astype(np.float64))
        rms = float(np.sqrt(np.mean(gh ** 2)))
        assert np.max(np.abs(np.asarray(lam) - gh)) < 2e-5 * rms
        assert np.max(np.abs(np.asarray(hes) - hh)) < 2e-5 * rms


def test_reference_ndcg_matches_eval_ndcg(rank_ref):
    ln, y, s, _ = _queries(12, nq=80, kind="no_relevant")
    rk = rank_ref.Ranking(ln, y, 30, EVAL_AT)
    qb = np.concatenate([[0], np.cumsum(ln)])
    want = [v for _, v, _ in eval_ndcg(s.astype(np.float64), y, qb, None,
                                       EVAL_AT, default_label_gain())]
    np.testing.assert_allclose(rk.ndcg(jnp.asarray(s)), want, atol=2e-6)


def test_reference_imports_nothing_of_the_program(rank_ref):
    src = open(rank_ref.__file__).read()
    assert "lightgbm_tpu" not in src.replace(
        "It imports nothing of the program under test", "")


def test_padded_lengths_are_powers_of_two_from_eight():
    ln = np.array([1, 7, 8, 9, 64, 65, 1251])
    np.testing.assert_array_equal(rank_buckets.padded_lengths(ln),
                                  [8, 8, 8, 16, 64, 128, 2048])


# ---- the held-out rows' walk as matrix products ------------------------
def _random_tree(rng, M, n_leaves):
    """Child arrays numbered as the growers number them: splitting leaf
    l at node j keeps l on the left and opens a new leaf on the right;
    nodes the tree does not have hold rubbish."""
    lc, rc = np.zeros(M, np.int32), np.zeros(M, np.int32)
    slot, nl = {0: None}, 1
    for j in range(n_leaves - 1):
        leaf = int(rng.integers(nl))
        if slot[leaf] is not None:
            (lc if slot[leaf][1] else rc)[slot[leaf][0]] = j
        lc[j], rc[j] = ~leaf, ~nl
        slot[leaf], slot[nl] = (j, True), (j, False)
        nl += 1
    dead = M - (n_leaves - 1)
    lc[n_leaves - 1:] = rng.integers(-5, 5, size=dead)
    rc[n_leaves - 1:] = rng.integers(-5, 5, size=dead)
    return lc, rc


@pytest.mark.parametrize("M,n_leaves,F,N", [
    (14, 15, 7, 1000), (254, 255, 137, 70000), (254, 1, 5, 100),
    (254, 2, 5, 100), (30, 17, 9, 33000)])
def test_path_matrix_walk_matches_the_gather_walk(M, n_leaves, F, N):
    """`predict_leaf_binned_paths` (what the TPU runs for a valid set's
    rows) against the lockstep gather walk: full, stump and one-split
    trees, missing-as-zero and missing-as-NaN columns, both defaults,
    row counts off the block size."""
    from lightgbm_tpu.models.tree import MISSING_NAN, MISSING_ZERO
    from lightgbm_tpu.ops import predict as pr
    from lightgbm_tpu.ops.split import FeatureMeta
    rng = np.random.default_rng(M + n_leaves)
    lc, rc = _random_tree(rng, M, n_leaves)
    meta = FeatureMeta(
        jnp.full((F,), 64, jnp.int32),
        jnp.asarray(rng.choice([0, MISSING_ZERO, MISSING_NAN], size=F)
                    .astype(np.int32)),
        jnp.asarray(rng.integers(0, 64, size=F).astype(np.int32)),
        jnp.zeros((F,), bool))
    args = [jnp.asarray(a) for a in (
        rng.integers(0, F, size=M).astype(np.int32),
        rng.integers(0, 62, size=M).astype(np.int32),
        rng.random(M) < 0.5, lc, rc)] + [
        jnp.int32(n_leaves),
        jnp.asarray(rng.integers(0, 64, size=(F, N)).astype(np.uint8)), meta]
    assert pr.path_walk_applies(args[6], None)
    want = np.asarray(jax.jit(pr.predict_leaf_binned)(*args))
    got = np.asarray(jax.jit(pr.predict_leaf_binned_paths)(*args))
    np.testing.assert_array_equal(got, want)
    assert want.min() >= 0 and want.max() < max(n_leaves, 1)


# ---- the chip's compiler, without the chip -----------------------------
@pytest.fixture(scope="module")
def one_chip():
    """A described v5e (libtpu's compile-only topology); nothing runs."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_path_walk_compiles_for_the_chip(one_chip):
    """At the widths of a 255-leaf tree over 136 columns. The first cut
    of `tree_paths` (float32 products at precision highest) passed every
    CPU test and took the TPU's compiler down with a failed check."""
    from lightgbm_tpu.ops import predict as pr
    from lightgbm_tpu.ops.split import FeatureMeta
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    M, F, N = 254, 136, 100000
    meta = FeatureMeta(S((F,), jnp.int32), S((F,), jnp.int32),
                       S((F,), jnp.int32), S((F,), jnp.bool_))
    compiled = jax.jit(pr.predict_leaf_binned_paths).lower(
        S((M,), jnp.int32), S((M,), jnp.int32), S((M,), jnp.bool_),
        S((M,), jnp.int32), S((M,), jnp.int32), S((), jnp.int32),
        S((F, N), jnp.uint8), meta).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_engine_train_replays_the_ndcg_columns():
    """`lgb.train` with a held-out ranking set stays in the scan (one
    dispatch a chunk) and hands callbacks ndcg@k after every tree."""
    X, y, ln = _table(1, 160)
    Xv, yv, lv = _table(2, 60)
    p = dict(PARAMS, batched_chunk_size=4)
    ds = lgb.Dataset(X, label=y, group=ln, params=p)
    dv = lgb.Dataset(Xv, label=yv, group=lv, reference=ds)
    seen = {}
    bst = lgb.train(p, ds, num_boost_round=8, valid_sets=[dv],
                    valid_names=["heldout"],
                    callbacks=[lgb.record_evaluation(seen)])
    assert bst._gbdt.dispatch_count == 2
    assert sorted(seen["heldout"]) == sorted(f"ndcg@{k}" for k in EVAL_AT)
    last = {n: v for _, n, v, _ in bst.eval_valid()}
    for name, vals in seen["heldout"].items():
        assert len(vals) == 8 and abs(vals[-1] - last[name]) < 1e-6
