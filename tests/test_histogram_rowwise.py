"""Row-wise multi-value histogram path (ops/histogram_rowwise.py,
docs/PERF.md) — the MultiValDenseBin analog: every used storage column's
bins in ONE flat per-feature-offset buffer, one kernel launch per wave.

Covers the full acceptance contract: interpret-mode kernel vs the pinned
flat XLA lowering, BITWISE identity with both the uniform XLA reference
and the col-wise tiered kernel (f32 exact-grid values and int8
quantized), EFB-bundled and mixed-width layouts, the dataset multi-value
pack (+ binary-cache round-trip), dispatch/eligibility fallback, the
autotune layout probe, and the force_row_wise/force_col_wise config
surface.
"""

import json
import os

import numpy as np
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.data.dataset import _multival_layout, _pack4
from lightgbm_tpu.ops.histogram import (_build_histogram_slots_xla,
                                        _build_histogram_xla, _tier_route)
from lightgbm_tpu.ops.histogram_rowwise import (
    CHUNK_COLS, OUT_VMEM_BYTES, RowWisePlan,
    _build_histogram_slots_rowwise_xla, build_histogram_rowwise,
    build_histogram_slots_rowwise, build_histogram_slots_rowwise_flat,
    build_histogram_slots_rowwise_packed_flat, build_pack4_plan,
    build_rowwise_plan, pack4, pack4_worthwhile, rowwise_eligible, rw_width)


def _bf16_exact_vals(rng, C, N):
    """Values on a 0.25 grid in [-8, 8): exact in bfloat16."""
    return (rng.randint(-32, 32, size=(C, N)) * 0.25).astype(np.float32)


def _inputs(nbins, N, rng):
    return np.stack([rng.randint(0, nb, N) for nb in nbins]).astype(np.uint8)


MIXED_NBINS = (33, 256, 12, 100, 256, 8, 64, 7)


# ---------------------------------------------------------------------------
# Plan / layout
# ---------------------------------------------------------------------------

def test_rw_width_exact_widths():
    assert rw_width(33) == 40          # not the 64-lane col-wise class
    assert rw_width(7) == 8
    assert rw_width(8) == 8
    assert rw_width(256) == 256
    assert rw_width(1) == 8
    with pytest.raises(ValueError):
        rw_width(257)


def test_plan_offsets_disjoint_and_chunked():
    plan = build_rowwise_plan(MIXED_NBINS)
    # offsets carve disjoint 8-aligned segments
    for f, (o, w) in enumerate(zip(plan.offsets, plan.widths)):
        assert o % 8 == 0 and w % 8 == 0
        assert w == rw_width(MIXED_NBINS[f])
    ends = [o + w for o, w in zip(plan.offsets, plan.widths)]
    assert all(plan.offsets[i + 1] >= ends[i]
               for i in range(len(ends) - 1))
    assert plan.total % 128 == 0
    # chunk bookkeeping: runs tile each chunk, cols lane-aligned
    for (col0, cols, runs) in plan.chunks:
        assert col0 % 128 == 0 and cols % 128 == 0
        assert sum(m * w for (_, m, w) in runs) <= cols <= CHUNK_COLS + 128


def test_plan_splits_into_multiple_chunks():
    plan = build_rowwise_plan((256,) * 20)      # 5120 flat cols
    assert len(plan.chunks) == 3
    assert plan.total == 20 * 256
    # every feature's segment lies inside its chunk
    for (col0, cols, runs) in plan.chunks:
        for (f0, m, w) in runs:
            for j in range(m):
                o = plan.offsets[f0 + j]
                assert col0 <= o and o + w <= col0 + cols


def test_plan_lockstep_with_dataset_layout():
    """build_rowwise_plan and the numpy twin in data/dataset.py must
    stay in arithmetic lockstep (the dataset computes offsets without
    importing jax)."""
    cases = [MIXED_NBINS, (255,) * 28, (2,) * 300, (256,) * 20,
             tuple(int(x) for x in
                   np.random.RandomState(0).randint(2, 257, size=64))]
    for nbins in cases:
        plan = build_rowwise_plan(tuple(nbins))
        lay = _multival_layout(list(nbins))
        assert lay is not None
        assert list(plan.offsets) == lay[0]
        assert list(plan.widths) == lay[1]
        assert plan.total == lay[2]
    assert _multival_layout([16, 300]) is None   # >8-bit storage: no plan


def test_rowwise_eligible_gates_on_output_bytes():
    plan = build_rowwise_plan(MIXED_NBINS)
    assert rowwise_eligible(plan, 2, 4)
    k_max = OUT_VMEM_BYTES // (2 * plan.total * 4)
    assert not rowwise_eligible(plan, 2, k_max + 1)
    assert not rowwise_eligible(RowWisePlan((), (), (), 0), 2, 1)


# ---------------------------------------------------------------------------
# Kernel parity (interpret mode on the CPU test platform)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nbins,K", [
    (MIXED_NBINS, 4),                 # mixed widths incl. two 256-bin cols
    ((15, 9, 4), 2),                  # all-narrow
    ((255,) * 5 + (63,) * 4, 8),      # wide + narrow at 255-bin config
    ((256,) * 20, 2),                 # multi-chunk flat buffer
])
def test_flat_matches_xla_reference(nbins, K):
    rng = np.random.RandomState(sum(nbins) % 9973)
    N, C = 1500, 3
    X = _inputs(nbins, N, rng)
    vals = _bf16_exact_vals(rng, C, N)
    # slots include inactive rows (slot == -1 and slot == K)
    slot = rng.randint(-1, K + 1, size=N).astype(np.int32)
    plan = build_rowwise_plan(nbins)
    got = build_histogram_slots_rowwise_flat(
        jnp.asarray(X), jnp.asarray(vals), jnp.asarray(slot), K, plan,
        interpret=True)
    ref = _build_histogram_slots_rowwise_xla(
        jnp.asarray(X), jnp.asarray(vals), jnp.asarray(slot), K, plan)
    assert got.shape == (K, C, plan.total)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("nbins,B,K", [
    (MIXED_NBINS, 256, 4),
    ((63, 63, 40, 7), 64, 3),
])
def test_expanded_bitwise_vs_uniform_and_tiered(nbins, B, K):
    """The expanded grid must be BITWISE identical to the uniform XLA
    reference AND the col-wise tiered kernel — the cross-layout
    acceptance contract: identical bf16 products in the same padded
    row-block order regardless of layout."""
    from lightgbm_tpu.ops.histogram_tiered import (
        build_histogram_slots_tiered, build_tier_plan)
    rng = np.random.RandomState(sum(nbins))
    N, C = 1500, 3
    X = _inputs(nbins, N, rng)
    vals = _bf16_exact_vals(rng, C, N)
    slot = rng.randint(-1, K + 1, size=N).astype(np.int32)
    rplan = build_rowwise_plan(nbins)
    got = np.asarray(build_histogram_slots_rowwise(
        jnp.asarray(X), jnp.asarray(vals), jnp.asarray(slot), K, B,
        rplan, interpret=True))
    ref = np.asarray(_build_histogram_slots_xla(
        jnp.asarray(X), jnp.asarray(vals), jnp.asarray(slot), K, B))
    col = np.asarray(build_histogram_slots_tiered(
        jnp.asarray(X), jnp.asarray(vals), jnp.asarray(slot), K, B,
        build_tier_plan(nbins), interpret=True))
    assert got.shape == (K, C, len(nbins), B)
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(got, col)


def test_quantized_int8_exact():
    """int8 gradients contract s8 x s8 -> s32: exact, no tolerance."""
    from lightgbm_tpu.ops.histogram_tiered import (
        build_histogram_slots_tiered, build_tier_plan)
    rng = np.random.RandomState(7)
    nbins, N, C, K, B = MIXED_NBINS, 1200, 2, 4, 256
    X = _inputs(nbins, N, rng)
    vals = rng.randint(-127, 128, size=(C, N)).astype(np.int8)
    slot = rng.randint(-1, K + 1, size=N).astype(np.int32)
    rplan = build_rowwise_plan(nbins)
    flat = build_histogram_slots_rowwise_flat(
        jnp.asarray(X), jnp.asarray(vals), jnp.asarray(slot), K, rplan,
        interpret=True)
    assert flat.dtype == jnp.int32
    ref_flat = _build_histogram_slots_rowwise_xla(
        jnp.asarray(X), jnp.asarray(vals), jnp.asarray(slot), K, rplan)
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(ref_flat))
    got = np.asarray(build_histogram_slots_rowwise(
        jnp.asarray(X), jnp.asarray(vals), jnp.asarray(slot), K, B,
        rplan, interpret=True))
    col = np.asarray(build_histogram_slots_tiered(
        jnp.asarray(X), jnp.asarray(vals), jnp.asarray(slot), K, B,
        build_tier_plan(nbins), interpret=True))
    np.testing.assert_array_equal(got, col)


def test_single_set_wrapper_matches_reference():
    rng = np.random.RandomState(11)
    nbins, N, C, B = (33, 256, 12, 7), 900, 3, 256
    X = _inputs(nbins, N, rng)
    vals = _bf16_exact_vals(rng, C, N)
    plan = build_rowwise_plan(nbins)
    got = build_histogram_rowwise(jnp.asarray(X), jnp.asarray(vals), B,
                                  plan, interpret=True)
    ref = _build_histogram_xla(jnp.asarray(X), jnp.asarray(vals), B)
    assert got.shape == (C, len(nbins), B)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_masked_rows_contribute_nothing():
    rng = np.random.RandomState(13)
    nbins, N, C, K = (100, 17, 256), 700, 2, 3
    X = _inputs(nbins, N, rng)
    vals = _bf16_exact_vals(rng, C, N)
    slot = rng.randint(0, K, size=N).astype(np.int32)
    keep = rng.rand(N) < 0.5
    plan = build_rowwise_plan(nbins)
    got = build_histogram_slots_rowwise_flat(
        jnp.asarray(X), jnp.asarray(vals * keep[None, :]),
        jnp.asarray(np.where(keep, slot, -1)), K, plan, interpret=True)
    ref = _build_histogram_slots_rowwise_xla(
        jnp.asarray(X[:, keep]), jnp.asarray(vals[:, keep]),
        jnp.asarray(slot[keep]), K, plan)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

def test_tier_route_rowwise():
    nbins = MIXED_NBINS
    r = _tier_route(nbins, len(nbins), 256, "rowwise")
    assert r[0] == "rowwise"
    assert r[1] == build_rowwise_plan(nbins)
    # sliced feature axis (shards, warm-up dummies): legacy, no plan
    assert _tier_route(nbins, len(nbins) - 1, 256, "rowwise") is None
    # >8-bit storage: no rowwise route
    assert _tier_route((300, 16), 2, 512, "rowwise") is None
    # "auto" stays col-wise: rowwise opts in via autotune or config only
    assert _tier_route(nbins, len(nbins), 256, "auto")[0] != "rowwise"


def test_dispatch_falls_back_when_ineligible(monkeypatch):
    """On a TPU backend the dispatcher re-routes col-wise when the flat
    output exceeds the VMEM budget; exercised here by forcing the
    pallas branch with interpret-mode kernels."""
    from lightgbm_tpu.ops import histogram as H
    calls = {}
    monkeypatch.setattr(H, "_use_pallas", lambda X, B: True)

    import lightgbm_tpu.ops.histogram_rowwise as HR

    real = HR.build_histogram_slots_rowwise

    def spy(*a, **k):
        calls["rowwise"] = True
        return real(*a, interpret=True, **{x: v for x, v in k.items()
                                           if x != "interpret"})

    monkeypatch.setattr(HR, "build_histogram_slots_rowwise", spy)
    rng = np.random.RandomState(3)
    nbins, N, C, B = (63, 12, 7), 400, 2, 64
    X = _inputs(nbins, N, rng)
    vals = _bf16_exact_vals(rng, C, N)
    slot = rng.randint(0, 2, size=N).astype(np.int32)
    got = H.build_histogram_slots(jnp.asarray(X), jnp.asarray(vals),
                                  jnp.asarray(slot), 2, B,
                                  tiers=nbins, impl="rowwise")
    assert calls.get("rowwise")
    ref = _build_histogram_slots_xla(jnp.asarray(X), jnp.asarray(vals),
                                     jnp.asarray(slot), 2, B)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # ineligible wave (huge K): must NOT call the rowwise kernel; the
    # col-wise fallback goes through the tiered path, which we stub to
    # observe the reroute without a real TPU kernel launch
    calls.clear()
    plan = build_rowwise_plan(nbins)
    k_big = OUT_VMEM_BYTES // (C * plan.total * 4) + 1
    from lightgbm_tpu.ops import histogram_tiered as HT
    monkeypatch.setattr(
        HT, "build_histogram_slots_tiered",
        lambda X, v, s, K, B, plan, hilo=True, interpret=False:
        ("colwise", K))
    out = H.build_histogram_slots(jnp.asarray(X), jnp.asarray(vals),
                                  jnp.asarray(slot), k_big, B,
                                  tiers=nbins, impl="rowwise")
    assert "rowwise" not in calls
    assert out == ("colwise", k_big)


# ---------------------------------------------------------------------------
# Dataset multi-value pack
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def efb_xy():
    rng = np.random.RandomState(3)
    X = rng.normal(size=(2000, 8)).astype(np.float64)
    onehot = (rng.randint(0, 6, size=(2000, 1))
              == np.arange(6)).astype(np.float64)
    X = np.hstack([X, onehot])
    y = (X[:, 0] > 0).astype(np.float32)
    return X, y


def test_dataset_multival_pack_and_layout(efb_xy):
    X, y = efb_xy
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    h = ds._handle
    assert h.bundles is not None          # the one-hots bundle
    mv = h.build_multival()
    assert mv is not None and mv.dtype == np.uint8
    assert mv.flags["C_CONTIGUOUS"]
    storage = h.X_bundled if h.bundles is not None else h.X_binned
    np.testing.assert_array_equal(mv, storage)
    # offsets come from the same arithmetic as the kernel plan, keyed on
    # per-STORAGE-column bin counts (bundles at their packed width)
    plan = build_rowwise_plan(tuple(h.storage_num_bins()))
    assert list(h.multival_offsets) == list(plan.offsets)
    assert list(h.multival_widths) == list(plan.widths)
    assert h.multival_total == plan.total
    assert h.build_multival() is mv       # cached, not rebuilt


def test_dataset_multival_binary_roundtrip(tmp_path, efb_xy):
    X, y = efb_xy
    ds = lgb.Dataset(X, label=y)
    path = str(tmp_path / "mv.bin")
    ds.save_binary(path)
    ds.construct()
    mv = ds._handle.build_multival()
    loaded = lgb.Dataset(path)
    loaded.construct()
    mv2 = loaded._handle.build_multival()
    np.testing.assert_array_equal(mv, mv2)
    assert list(loaded._handle.multival_offsets) \
        == list(ds._handle.multival_offsets)
    assert loaded._handle.multival_total == ds._handle.multival_total


# ---------------------------------------------------------------------------
# Training surface: config, force_* escape hatches, autotune
# ---------------------------------------------------------------------------

def _xy(n=1200, f=10, seed=5):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] * 2 + np.sin(X[:, 1])).astype(np.float32)
    return X, y


BASE = {"objective": "regression", "num_leaves": 15, "max_bin": 63,
        "min_data_in_leaf": 5, "verbose": -1, "deterministic": True}


def test_rowwise_training_matches_colwise():
    X, y = _xy()
    preds = {}
    for name, extra in [("col", {}),
                        ("row", {"histogram_impl": "rowwise"}),
                        ("force_row", {"force_row_wise": True}),
                        ("force_col", {"force_col_wise": True})]:
        p = dict(BASE, **extra)
        preds[name] = lgb.train(p, lgb.Dataset(X, label=y),
                                num_boost_round=5).predict(X)
    np.testing.assert_array_equal(preds["col"], preds["row"])
    np.testing.assert_array_equal(preds["col"], preds["force_row"])
    np.testing.assert_array_equal(preds["col"], preds["force_col"])


def test_config_rowwise_validation():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.utils.log import FatalError
    assert Config(histogram_impl="rowwise").histogram_impl == "rowwise"
    assert Config(force_row_wise=True).force_row_wise
    with pytest.raises(FatalError):
        Config(force_col_wise=True, force_row_wise=True)
    with pytest.raises(FatalError):
        Config(force_row_wise=True, histogram_impl="tiered")
    with pytest.raises(FatalError):
        Config(force_col_wise=True, histogram_impl="rowwise")
    # compatible combinations pass
    assert Config(force_row_wise=True,
                  histogram_impl="rowwise").force_row_wise
    assert Config(force_col_wise=True,
                  histogram_impl="tiered_hilo").force_col_wise


def test_autotune_probe_times_rowwise_layout():
    from lightgbm_tpu.runtime import autotune as at

    class FakeCfg:
        num_bins_padded = 64
        rows_per_chunk = 8192
        hist_tiers = (33, 64, 12, 7)

    rng = np.random.RandomState(0)
    X_t = jnp.asarray(rng.randint(0, 7, size=(4, 2048)).astype(np.uint8))
    t = at.probe_hist_impls(X_t, FakeCfg,
                            impl_candidates=at.HIST_IMPL_CANDIDATES,
                            probe_rows=1024)
    assert set(t) == set(at.HIST_IMPL_CANDIDATES)
    assert all(v > 0 for v in t.values())
    cols = at.probe_hist_impls(X_t, FakeCfg,
                               impl_candidates=at.COL_WISE_HIST_IMPLS,
                               probe_rows=1024)
    assert "rowwise" not in cols


def test_autotune_decision_cache_respects_candidates(tmp_path):
    """Decision cache round-trip, and the force_col_wise contract: a
    cached rowwise pick is NOT honored when the candidate set excludes
    it — the probe re-runs restricted."""
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
    assert set(dec["hist_impl_timings"]) == set(at.HIST_IMPL_CANDIDATES)
    assert at.autotune_decision(X_t, None, FakeCfg, (),
                                **kw)["cached"] == "memory"
    at._MEM_CACHE.clear()
    assert at.autotune_decision(X_t, None, FakeCfg, (),
                                **kw)["cached"] == "disk"
    # poison the cache with a rowwise pick, then ask col-wise-only
    at._MEM_CACHE.clear()
    with open(path) as fh:
        blob = json.load(fh)
    blob[dec["key"]]["hist_impl"] = "rowwise"
    with open(path, "w") as fh:
        json.dump(blob, fh)
    dec2 = at.autotune_decision(
        X_t, None, FakeCfg, (), **kw,
        hist_impl_candidates=at.COL_WISE_HIST_IMPLS)
    assert dec2["cached"] is False
    assert dec2["hist_impl"] in (None, *at.COL_WISE_HIST_IMPLS)
    assert "rowwise" not in dec2["hist_impl_timings"]


# ---------------------------------------------------------------------------
# 4-bit pack (Pack4Plan): the nibble pack reproduces the unpacked row-wise
# flat buffer bit-for-bit (same codes -> same one-hot products)
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
# 4-bit pack: dispatch, config, autotune
# ---------------------------------------------------------------------------

def test_tier_route_new_impls():
    tiers = (3, 2, 16, 5, 33, 2)
    r = _tier_route(tiers, len(tiers), 64, "rowwise_packed")
    assert r[0] == "rowwise_packed"
    assert r[1] == build_rowwise_plan(tiers)
    assert r[2] == build_pack4_plan(tiers)
    # nothing packable: silently the plain rowwise route
    wide = (33, 64, 250)
    assert _tier_route(wide, 3, 256, "rowwise_packed") \
        == _tier_route(wide, 3, 256, "rowwise")


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
    for impl in ("auto", "rowwise", "rowwise_packed"):
        p = dict(base, histogram_impl=impl)
        preds[impl] = lgb.train(p, lgb.Dataset(X, label=y),
                                num_boost_round=5).predict(X)
    for impl in ("rowwise", "rowwise_packed"):
        np.testing.assert_array_equal(preds["auto"], preds[impl])


def test_config_accepts_new_impls():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.utils.log import FatalError
    assert Config(histogram_impl="rowwise_packed",
                  force_row_wise=True).force_row_wise
    with pytest.raises(FatalError):
        Config(histogram_impl="rowwise_packed", force_col_wise=True)


def test_autotune_probe_includes_packed():
    from lightgbm_tpu.runtime import autotune as at
    assert "rowwise_packed" in at.HIST_IMPL_CANDIDATES
    assert "rowwise_packed" not in at.COL_WISE_HIST_IMPLS

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
