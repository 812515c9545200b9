"""Fused wave megakernel (ops/grow_fused.py) and the 4-bit packed
row-wise path (ops/histogram_rowwise.py Pack4Plan) vs the two-pass /
unpacked kernels they replace.

Bit-identity contract (docs/PERF.md): the fused kernel's relabel +
histogram output must equal `wave_pass_pallas` exactly, and its
in-kernel split scan must reproduce `split.py:find_best_split` on the
two-pass histogram field-for-field — it runs the REAL search tracer on
the VMEM-resident accumulators, so any divergence is a kernel bug, not
float noise. Likewise the nibble pack must reproduce the unpacked
row-wise flat buffer bit-for-bit (same codes -> same one-hot products).
Kernels run interpret=True on the CPU mesh, like the other Pallas
suites; the grower-level gate is exercised through the dispatch tests.
"""

import json

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.data.dataset import _pack4
from lightgbm_tpu.ops.grow_fused import (REC_ROWS, pack_fused_meta,
                                         pack_fused_scalars, rec_width,
                                         unpack_fused_records,
                                         wave_pass_fused_pallas)
from lightgbm_tpu.ops.histogram_pallas import wave_pass_pallas
from lightgbm_tpu.ops.histogram_rowwise import (
    build_histogram_slots_rowwise_flat,
    build_histogram_slots_rowwise_packed_flat, build_pack4_plan,
    build_rowwise_plan, pack4, pack4_worthwhile)
from lightgbm_tpu.ops.split import (FeatureMeta, SplitHyperParams,
                                    SplitResult, find_best_split,
                                    synth_count_channel)

MT_NONE, MT_ZERO, MT_NAN = 0, 1, 2

HP = SplitHyperParams(min_data_in_leaf=5.0, min_sum_hessian_in_leaf=1e-3,
                      lambda_l1=0.0, lambda_l2=0.0, max_delta_step=0.0,
                      min_gain_to_split=0.0, path_smooth=0.0)


def _wave_problem(B, F, N, K, KMAX, seed):
    """Synthesize one mid-tree wave: rows spread over 12 leaves, K of
    them candidates, plus applied relabel entries and per-candidate
    parent histograms that dominate the smaller-child accumulation."""
    rng = np.random.RandomState(seed)
    C = 2
    X = rng.randint(0, B - 1, size=(F, N)).astype(np.uint8)
    vals = (rng.randint(-32, 32, size=(C, N)) * 0.25).astype(np.float32)
    lor = rng.randint(0, 12, size=N).astype(np.int32)
    mts = rng.choice([MT_NONE, MT_ZERO, MT_NAN], size=KMAX)
    tblr = [np.array([0, 3, 5, 7] + [-1] * (KMAX - 4)),
            rng.randint(0, F, size=KMAX), rng.randint(0, B - 2, size=KMAX),
            rng.randint(0, 2, size=KMAX), mts,
            rng.randint(0, B - 1, size=KMAX), np.full(KMAX, B - 1),
            np.array([0, 12, 3, 13] + [-1] * (KMAX - 4))[:KMAX],
            rng.randint(0, F, size=KMAX), rng.randint(0, B - 2, size=KMAX),
            rng.randint(0, 2, size=KMAX), mts,
            rng.randint(0, B - 1, size=KMAX), np.full(KMAX, B - 1),
            rng.randint(0, 2, size=KMAX), np.full(KMAX, 12)]
    tbl_np = np.stack([np.asarray(t, np.int32) for t in tblr])
    tbl16 = jnp.asarray(np.pad(tbl_np, ((0, 0), (0, 128 - KMAX)),
                               constant_values=-1))
    parent = np.abs(rng.normal(size=(KMAX, C, F, B))
                    ).astype(np.float32) * 50
    meta = FeatureMeta(
        num_bins=jnp.full((F,), B - 1, jnp.int32),
        missing_type=jnp.asarray(
            rng.choice([MT_NONE, MT_ZERO, MT_NAN], size=F)
            .astype(np.int32)),
        default_bin=jnp.asarray(rng.randint(0, B - 1, size=F)
                                .astype(np.int32)),
        is_categorical=jnp.zeros((F,), bool),
    )

    class BS:
        left_sum_g = jnp.asarray(rng.normal(size=KMAX).astype(np.float32))
        left_sum_h = jnp.asarray(
            (np.abs(rng.normal(size=KMAX)) * 30 + 5).astype(np.float32))
        left_count = jnp.asarray(
            rng.randint(20, 200, size=KMAX).astype(np.float32))
        left_output = jnp.asarray(
            (rng.normal(size=KMAX) * 0.1).astype(np.float32))
        right_sum_g = jnp.asarray(rng.normal(size=KMAX).astype(np.float32))
        right_sum_h = jnp.asarray(
            (np.abs(rng.normal(size=KMAX)) * 30 + 5).astype(np.float32))
        right_count = jnp.asarray(
            rng.randint(20, 200, size=KMAX).astype(np.float32))
        right_output = jnp.asarray(
            (rng.normal(size=KMAX) * 0.1).astype(np.float32))

    sil = jnp.asarray(tblr[14].astype(np.float32))
    return X, vals, lor, tbl16, parent, meta, BS, sil


@pytest.mark.parametrize("B,F,wide_lo", [(32, 9, 128), (64, 9, 128),
                                         (128, 6, 128), (256, 4, 64)])
def test_fused_matches_two_pass(B, F, wide_lo):
    """Fused single-launch wave vs wave_pass_pallas + the XLA search:
    relabel and histogram bitwise, every SplitResult field bitwise, per
    lane-width class (256 runs the hi/lo decomposition the grower
    selects via mega_wide_lo)."""
    N, K, KMAX = 1200, 4, 8
    X, vals, lor, tbl16, parent, meta, BS, sil = _wave_problem(
        B, F, N, K, KMAX, seed=55 + B)
    scal = pack_fused_scalars(BS, sil, KMAX)
    meta_ops = pack_fused_meta(meta.num_bins, meta.missing_type,
                               meta.default_bin, meta.is_categorical)
    ref_lor, ref_hist = wave_pass_pallas(
        jnp.asarray(X), jnp.asarray(vals), jnp.asarray(lor), tbl16, K, B,
        interpret=True)
    got_lor, got_hist, rec = wave_pass_fused_pallas(
        jnp.asarray(X), jnp.asarray(vals), jnp.asarray(lor), tbl16,
        jnp.asarray(parent.reshape(KMAX, -1)), scal, meta_ops, K, B,
        KMAX, HP, interpret=True, wide_lo=wide_lo)
    np.testing.assert_array_equal(np.asarray(ref_lor), np.asarray(got_lor))
    np.testing.assert_array_equal(np.asarray(ref_hist),
                                  np.asarray(got_hist))

    s = unpack_fused_records(rec, KMAX)
    silb = np.asarray(sil) > 0
    F_ = X.shape[0]
    for j in range(2 * K):
        k = j % K
        is_left = j < K
        small = np.asarray(ref_hist)[k]
        ch = small if is_left == silb[k] else parent[k] - small
        sgv = (BS.left_sum_g if is_left else BS.right_sum_g)[k]
        shv = (BS.left_sum_h if is_left else BS.right_sum_h)[k]
        cv = (BS.left_count if is_left else BS.right_count)[k]
        ov = (BS.left_output if is_left else BS.right_output)[k]
        h3 = synth_count_channel(jnp.asarray(ch), cv, shv)
        res = find_best_split(h3, sgv, shv, cv, ov, meta, HP,
                              jnp.ones((F_,), bool))
        col = k if is_left else KMAX + k
        got = SplitResult(*[np.asarray(x)[col] for x in s])
        for name, a, b in zip(SplitResult._fields, res, got):
            assert np.array_equal(np.asarray(a), np.asarray(b),
                                  equal_nan=True), \
                f"child {j} field {name}: ref {np.asarray(a)} got {b}"
    # padded candidate columns carry zero records (the grower's
    # valid-masked scatter discards them, but garbage would mask bugs)
    r = np.asarray(rec)
    assert np.all(r[:, K:KMAX] == 0)
    assert np.all(r[:, KMAX + K:2 * KMAX] == 0)
    assert rec.shape == (REC_ROWS, rec_width(KMAX))


# ---------------------------------------------------------------------------
# 4-bit pack
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tiers", [
    (3, 2, 16, 5, 33, 2, 2, 9, 250, 16),   # mixed widths
    (2, 3, 2, 5, 7, 2, 3),                 # all packable, odd count
    (4, 4, 4, 4),                          # all packable, even count
])
def test_packed_rowwise_bitwise(tiers):
    rng = np.random.RandomState(11)
    F, N, K, C = len(tiers), 1500, 3, 2
    X = np.stack([rng.randint(0, t, size=N)
                  for t in tiers]).astype(np.uint8)
    vals = (rng.randint(-32, 32, size=(C, N)) * 0.25).astype(np.float32)
    slot = rng.randint(-1, K, size=N).astype(np.int32)
    rplan = build_rowwise_plan(tiers)
    pplan = build_pack4_plan(tiers)
    assert pack4_worthwhile(pplan)
    ref = build_histogram_slots_rowwise_flat(
        jnp.asarray(X), jnp.asarray(vals), jnp.asarray(slot), K, rplan,
        interpret=True)
    Xp, Xu = pack4(jnp.asarray(X), pplan)
    assert Xp.shape[0] == (pplan.n_packed + 1) // 2
    got = build_histogram_slots_rowwise_packed_flat(
        Xp, Xu, jnp.asarray(vals), jnp.asarray(slot), K, rplan, pplan,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    # numpy twin (data/dataset.py) packs bit-identically to the device op
    out = _pack4(np.ascontiguousarray(X.T), tiers)
    packed_np, rest_np, pp, rp = out
    assert list(pp) == list(pplan.pack_pos)
    assert list(rp) == list(pplan.rest_pos)
    np.testing.assert_array_equal(packed_np.T,
                                  np.asarray(Xp).astype(np.uint8))
    np.testing.assert_array_equal(rest_np.T,
                                  np.asarray(Xu).astype(np.uint8))


def test_packed_rowwise_quantized_int8():
    tiers = (3, 2, 16, 5, 33, 2)
    rng = np.random.RandomState(12)
    N, K, C = 1024, 2, 2
    X = np.stack([rng.randint(0, t, size=N)
                  for t in tiers]).astype(np.uint8)
    vals = rng.randint(-100, 100, size=(C, N)).astype(np.int8)
    slot = rng.randint(-1, K, size=N).astype(np.int32)
    rplan = build_rowwise_plan(tiers)
    pplan = build_pack4_plan(tiers)
    ref = build_histogram_slots_rowwise_flat(
        jnp.asarray(X), jnp.asarray(vals), jnp.asarray(slot), K, rplan,
        interpret=True)
    Xp, Xu = pack4(jnp.asarray(X), pplan)
    got = build_histogram_slots_rowwise_packed_flat(
        Xp, Xu, jnp.asarray(vals), jnp.asarray(slot), K, rplan, pplan,
        interpret=True)
    assert np.asarray(got).dtype == np.int32   # exact s8xs8->s32
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_pack4_not_worthwhile_below_two_columns():
    assert not pack4_worthwhile(build_pack4_plan((33, 64, 250)))
    assert not pack4_worthwhile(build_pack4_plan((7, 33)))
    assert _pack4(np.zeros((10, 2), np.uint8), (7, 33)) is None


def test_dataset_packed_multival_efb():
    """EFB bundles pack for free: a bundle column is a storage column
    with a packed bin count, and <=16-bin bundles take a nibble."""
    rng = np.random.RandomState(3)
    X = rng.normal(size=(2000, 8)).astype(np.float64)
    onehot = (rng.randint(0, 6, size=(2000, 1))
              == np.arange(6)).astype(np.float64)
    X = np.hstack([X, onehot])
    y = (X[:, 0] > 0).astype(np.float32)
    # max_bin=15 keeps the numeric columns nibble-sized too, so the pack
    # covers raw columns AND the bundle column in one plan
    ds = lgb.Dataset(X, label=y, params={"max_bin": 15})
    ds.construct()
    h = ds._handle
    assert h.bundles is not None
    out = h.build_multival_packed()
    assert out is not None
    packed, rest, pack_pos, rest_pos = out
    tiers = tuple(int(t) for t in h.storage_num_bins())
    # the one-hot bundle (6 members, 2 bins each -> 7-bin column) must
    # have landed in a nibble
    assert any(t <= 16 for t in tiers)
    pplan = build_pack4_plan(tiers)
    assert list(pack_pos) == list(pplan.pack_pos)
    assert list(rest_pos) == list(pplan.rest_pos)
    # host pack == device pack of the same storage matrix
    Xp, Xu = pack4(jnp.asarray(h.build_multival().T), pplan)
    np.testing.assert_array_equal(packed.T, np.asarray(Xp).astype(np.uint8))
    np.testing.assert_array_equal(rest.T, np.asarray(Xu).astype(np.uint8))
    assert h.build_multival_packed() is out or \
        h.build_multival_packed()[0] is packed   # cached, not rebuilt


# ---------------------------------------------------------------------------
# Dispatch, autotune, decision cache
# ---------------------------------------------------------------------------

def test_tier_route_new_impls():
    from lightgbm_tpu.ops.histogram import _tier_route
    tiers = (3, 2, 16, 5, 33, 2)
    r = _tier_route(tiers, len(tiers), 64, "rowwise_packed")
    assert r[0] == "rowwise_packed"
    assert r[1] == build_rowwise_plan(tiers)
    assert r[2] == build_pack4_plan(tiers)
    # nothing packable: silently the plain rowwise route
    wide = (33, 64, 250)
    assert _tier_route(wide, 3, 256, "rowwise_packed") \
        == _tier_route(wide, 3, 256, "rowwise")
    # "fused" has no plain-histogram form: routes like "auto"
    assert _tier_route(tiers, len(tiers), 64, "fused") \
        == _tier_route(tiers, len(tiers), 64, "auto")


def test_training_parity_new_impls():
    """End-to-end dispatch: every impl must produce the identical model
    (on the CPU mesh the Pallas gate falls back to the pinned XLA path,
    which is exactly the escape-hatch contract)."""
    rng = np.random.RandomState(5)
    X = rng.normal(size=(1200, 10)).astype(np.float32)
    y = (X[:, 0] * 2 + np.sin(X[:, 1])).astype(np.float32)
    base = {"objective": "regression", "num_leaves": 15, "max_bin": 15,
            "min_data_in_leaf": 5, "verbose": -1, "deterministic": True}
    preds = {}
    for impl in ("auto", "rowwise", "rowwise_packed", "fused"):
        p = dict(base, histogram_impl=impl)
        preds[impl] = lgb.train(p, lgb.Dataset(X, label=y),
                                num_boost_round=5).predict(X)
    for impl in ("rowwise", "rowwise_packed", "fused"):
        np.testing.assert_array_equal(preds["auto"], preds[impl])


def test_config_accepts_new_impls():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.utils.log import FatalError
    assert Config(histogram_impl="fused").histogram_impl == "fused"
    assert Config(histogram_impl="rowwise_packed",
                  force_row_wise=True).force_row_wise
    assert Config(histogram_impl="fused", force_col_wise=True).force_col_wise
    with pytest.raises(FatalError):
        Config(histogram_impl="rowwise_packed", force_col_wise=True)
    with pytest.raises(FatalError):
        Config(histogram_impl="fused", force_row_wise=True)


def test_autotune_probe_includes_packed():
    from lightgbm_tpu.runtime import autotune as at
    assert "rowwise_packed" in at.HIST_IMPL_CANDIDATES
    assert "rowwise_packed" not in at.COL_WISE_HIST_IMPLS
    assert "fused" not in at.HIST_IMPL_CANDIDATES

    class FakeCfg:
        num_bins_padded = 16
        rows_per_chunk = 8192
        hist_tiers = (12, 7, 8, 16)

    rng = np.random.RandomState(0)
    X_t = jnp.asarray(rng.randint(0, 7, size=(4, 1024)).astype(np.uint8))
    t = at.probe_hist_impls(X_t, FakeCfg,
                            impl_candidates=at.HIST_IMPL_CANDIDATES,
                            probe_rows=512)
    assert "rowwise_packed" in t and t["rowwise_packed"] > 0


def test_probe_fused_wave_cpu_graceful():
    """On a non-TPU backend the Pallas launches fail and both probe arms
    drop — the decision keeps the unfused wave instead of crashing."""
    from lightgbm_tpu.runtime import autotune as at

    class FakeCfg:
        num_bins_padded = 16
        rows_per_chunk = 8192
        hist_tiers = (12, 7, 8, 16)

    rng = np.random.RandomState(0)
    X_t = jnp.asarray(rng.randint(0, 7, size=(4, 1024)).astype(np.uint8))
    t = at.probe_fused_wave(X_t, FakeCfg, probe_rows=512)
    assert "fused" not in t


def test_decision_cache_accepts_fused(tmp_path):
    """A cached hist_impl='fused' decision (written by a TPU run) must
    hit, not re-probe: 'fused' never rides the plain-histogram candidate
    list, so the acceptance check has to allow it explicitly."""
    from lightgbm_tpu.runtime import autotune as at

    class FakeCfg:
        num_bins_padded = 16
        rows_per_chunk = 8192
        hist_tiers = (12, 7, 8, 16)
        hist_impl = "auto"

    rng = np.random.RandomState(0)
    X_t = jnp.asarray(rng.randint(0, 7, size=(4, 1024)).astype(np.uint8))
    path = str(tmp_path / "autotune.json")
    kw = dict(n_rows=1024, n_features=4, max_bin=15, num_leaves=31,
              cache_path=path, probe_rows=512, tune_chunks=False)
    at._MEM_CACHE.clear()
    dec = at.autotune_decision(X_t, None, FakeCfg, (), **kw)
    assert dec["cached"] is False
    assert "fused_wave_timings" in dec
    with open(path) as fh:
        blob = json.load(fh)
    blob[dec["key"]]["hist_impl"] = "fused"
    with open(path, "w") as fh:
        json.dump(blob, fh)
    at._MEM_CACHE.clear()
    hit = at.autotune_decision(X_t, None, FakeCfg, (), **kw)
    assert hit["cached"] == "disk"
    assert hit["hist_impl"] == "fused"
    # and a second call rides the memory cache
    assert at.autotune_decision(X_t, None, FakeCfg, (),
                                **kw)["cached"] == "memory"


# ---------------------------------------------------------------------------
# feature-tiled megakernel: the same bit-identity contract past F <= 32 and
# in every regime the fused path used to veto (quantized gradients,
# monotone basic, interaction sets, categorical bitsets), exercised
# end-to-end through the grower with every Pallas kernel interpreted.
# ---------------------------------------------------------------------------

INTERP = "LIGHTGBM_TPU_PALLAS_INTERPRET"
TILED_BASE = {"objective": "regression", "num_leaves": 15, "max_bin": 31,
              "min_data_in_leaf": 5, "verbose": -1, "deterministic": True}


def _tiled_parity(monkeypatch, F, extra=None, max_bin=31, n=500,
                  rounds=2, cat_cols=(), seed=3):
    """Train histogram_impl='fused' vs the two-pass wave ('auto') with
    identical data and require byte-identical predictions: the tiled
    megakernel runs the real relabel/histogram/search tracers on its
    VMEM accumulators, so any divergence is a kernel bug."""
    monkeypatch.setenv(INTERP, "1")
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    for c in cat_cols:
        X[:, c] = rng.randint(0, 9, size=n)
    y = (X[:, 0] - 0.5 * X[:, F // 2] + np.sin(X[:, 1])).astype(np.float32)
    preds = {}
    for impl in ("auto", "fused"):
        p = dict(TILED_BASE, histogram_impl=impl, max_bin=max_bin,
                 **(extra or {}))
        ds = (lgb.Dataset(X, label=y, categorical_feature=list(cat_cols))
              if cat_cols else lgb.Dataset(X, label=y))
        preds[impl] = lgb.train(p, ds, num_boost_round=rounds).predict(X)
    np.testing.assert_array_equal(preds["auto"], preds["fused"])


@pytest.mark.parametrize("F", [33, 64, 100])
def test_tiled_parity_wide(F, monkeypatch):
    """Tile-multiple and tail widths: 33 (1 tile + 1-col tail), 64
    (exactly 2 tiles), 100 (3 tiles + 4-col tail)."""
    _tiled_parity(monkeypatch, F, n=400)


def test_tiled_parity_wide_bins_tail(monkeypatch):
    # 255 features (7 full tiles + 31-wide tail) on the 256-lane bin axis
    _tiled_parity(monkeypatch, 255, max_bin=255, n=300, rounds=1)


def test_tiled_parity_quantized(monkeypatch):
    _tiled_parity(monkeypatch, 50, extra={"use_quantized_grad": True},
                  n=400)


def test_tiled_parity_monotone_basic(monkeypatch):
    mc = [1, -1] * 20
    _tiled_parity(monkeypatch, 40,
                  extra={"monotone_constraints": mc,
                         "monotone_constraints_method": "basic"}, n=400)


def test_tiled_parity_interaction_sets(monkeypatch):
    sets = [list(range(0, 14)), list(range(10, 26)), list(range(24, 40))]
    _tiled_parity(monkeypatch, 40,
                  extra={"interaction_constraints": sets}, n=400)


def test_tiled_parity_categorical(monkeypatch):
    _tiled_parity(monkeypatch, 40, cat_cols=(0, 3, 7, 11),
                  extra={"max_cat_to_onehot": 4,
                         "max_cat_threshold": 16}, n=400)


def test_tiled_parity_relabel_fusion_off(monkeypatch):
    """fused_relabel_fusion=false keeps the separate wave_apply relabel
    launch; results must not move either way."""
    _tiled_parity(monkeypatch, 40,
                  extra={"fused_relabel_fusion": False}, n=400)


def test_relabel_fusion_cuts_launch_sites(monkeypatch):
    """Launches-per-tree regression gate (the dispatch_count analog):
    folding the RELABEL pass of applies-only waves into the next
    SPECULATE launch must remove its Pallas site from the wave body."""
    monkeypatch.setenv(INTERP, "1")
    from lightgbm_tpu.ops.grow_wave import grow_tree_wave
    from lightgbm_tpu.runtime.profiler import count_pallas_launch_sites
    rng = np.random.RandomState(0)
    X = rng.normal(size=(400, 40)).astype(np.float32)
    y = X[:, 0].astype(np.float32)
    bst = lgb.train(dict(TILED_BASE, histogram_impl="fused"),
                    lgb.Dataset(X, label=y), num_boost_round=1)
    g = bst._gbdt
    n = int(g.X_t.shape[1])
    grad = jnp.asarray(rng.normal(size=n).astype(np.float32))
    hess = jnp.ones((n,), jnp.float32)
    bag = jnp.ones((n,), jnp.float32)

    def sites(cfg):
        return count_pallas_launch_sites(
            lambda: grow_tree_wave(g.X_t, grad, hess, bag, g.meta, cfg))

    on = sites(g.grow_cfg._replace(hist_impl="fused",
                                   fused_relabel_fusion=True))
    off = sites(g.grow_cfg._replace(hist_impl="fused",
                                    fused_relabel_fusion=False))
    assert on > 0
    assert on < off


def test_fused_observability_extras(monkeypatch):
    """Every train records WHY the fused path is (in)eligible: empty
    veto list + launch geometry when it runs, the veto reasons when it
    silently would not."""
    monkeypatch.setenv(INTERP, "1")
    rng = np.random.RandomState(1)
    X = rng.normal(size=(400, 40)).astype(np.float32)
    y = X[:, 0].astype(np.float32)
    p = dict(TILED_BASE, histogram_impl="fused", device_profile=True)
    bst = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=1)
    prof = bst._gbdt.profiler
    assert prof.extras["fused_veto_reasons"] == []
    fused = prof.extras["fused"]
    assert fused["path"] == "fused_tiled"
    assert fused["feature_tile"] == 32 and fused["feature_tiles"] == 2
    assert fused["relabel_fusion"] is True
    assert "fused" in prof.to_dict()
    assert bst._gbdt.grow_cfg.fused_feature_tile == 32

    monkeypatch.setenv("LIGHTGBM_TPU_DISABLE_FUSED", "1")
    bst2 = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=1)
    vetoes = bst2._gbdt.profiler.extras["fused_veto_reasons"]
    assert "LIGHTGBM_TPU_DISABLE_FUSED" in vetoes


def test_fused_config_knobs():
    from lightgbm_tpu.config import Config, resolve_params
    from lightgbm_tpu.utils.log import FatalError
    assert resolve_params({"fused_tile": 64}).fused_feature_tile == 64
    assert not resolve_params(
        {"relabel_fusion": False}).fused_relabel_fusion
    with pytest.raises(FatalError):
        Config(fused_feature_tile=48)
    # customizing fused geometry under a non-fused histogram pin is the
    # force_row_wise contradiction class: fail fast, don't no-op
    with pytest.raises(FatalError):
        Config(fused_feature_tile=64, histogram_impl="rowwise")
    with pytest.raises(FatalError):
        Config(fused_relabel_fusion=False, histogram_impl="tiered")
    Config(histogram_impl="rowwise")      # defaults: no contradiction
    Config(fused_feature_tile=128, histogram_impl="fused")
    # orchestration-only: excluded from the model-file parameter echo
    echo = Config().to_string()
    assert "fused_feature_tile" not in echo
    assert "fused_relabel_fusion" not in echo


def test_fused_variant_sig_keys_decision_cache():
    """Non-default tile/fusion settings must produce a DIFFERENT cache
    key (a decision probed at one geometry must not leak into another),
    while the default signature keeps the historical unsuffixed keys."""
    from lightgbm_tpu.runtime import autotune as at

    class Cfg:
        fused_feature_tile = 32
        fused_relabel_fusion = True

    assert at.fused_variant_sig(Cfg) == ""
    Cfg.fused_feature_tile = 64
    sig = at.fused_variant_sig(Cfg)
    assert sig == "t64rf1" and sig != at._DEFAULT_FUSED_SIG
    k0 = at.make_key(1000, 10, 255, 31)
    assert at.make_key(1000, 10, 255, 31, variant="") == k0
    k1 = at.make_key(1000, 10, 255, 31, variant=sig)
    assert k1 != k0 and k1.endswith("_" + sig)


def test_fused_selected_on_chip_raises(monkeypatch):
    """histogram_impl=fused without interpret mode on a TPU backend fails
    at trace time instead of dropping out of a probe."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rng = np.random.RandomState(0)
    X = rng.normal(size=(400, 8)).astype(np.float32)
    p = dict(objective="binary", num_leaves=4, max_bin=15, verbose=-1,
             histogram_impl="fused", binning_impl="host")
    bst = lgb.Booster(params=p, train_set=lgb.Dataset(
        X, label=(X[:, 0] > 0).astype(np.float32), params=p))
    with pytest.raises(NotImplementedError, match="histogram_impl=fused"):
        bst.update()
