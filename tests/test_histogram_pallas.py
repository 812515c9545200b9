"""Pallas histogram kernels vs the portable XLA lowering (interpret mode on
the CPU test platform; the same kernels compile for real TPUs).

The Pallas kernels contract in bfloat16 (f32 accumulation). Exactness tests
use values on a coarse binary grid (exactly representable in bf16, so the
products and f32 sums are exact); a separate test bounds the bf16 rounding
error for continuous values.
"""

import numpy as np
import jax.numpy as jnp
import pytest

# pin the reference to the XLA body: on a TPU backend the public
# build_histogram would dispatch to the very kernel under test
from lightgbm_tpu.ops.histogram import (_build_histogram_xla,
                                        _build_histogram_slots_xla)
from lightgbm_tpu.ops.histogram_pallas import (build_histogram_pallas,
                                               build_histogram_slots_pallas)


def _bf16_exact_vals(rng, C, N):
    """Values on a 0.25 grid in [-8, 8): exact in bfloat16."""
    return (rng.randint(-32, 32, size=(C, N)) * 0.25).astype(np.float32)


@pytest.mark.parametrize("F,N,C,B,hi", [
    (28, 5000, 6, 256, 250),   # full 8-bit bin range (incl. bins >= 128)
    (5, 1000, 3, 64, 63),      # small bin count
    (1, 100, 1, 16, 15),       # tiny
    (33, 2048, 6, 136, 135),   # F crosses one block; B needs padding
])
def test_matches_xla_lowering(F, N, C, B, hi):
    rng = np.random.RandomState(F * 1000 + N)
    X = rng.randint(0, hi, size=(F, N)).astype(np.uint8)
    vals = _bf16_exact_vals(rng, C, N)
    ref = _build_histogram_xla(jnp.asarray(X), jnp.asarray(vals), B)
    got = build_histogram_pallas(jnp.asarray(X), jnp.asarray(vals), B,
                                 interpret=True)
    assert got.shape == (C, F, B)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("F,N,C,B,K", [
    (7, 3000, 3, 64, 8),
    (28, 4096, 3, 256, 16),
    (3, 500, 3, 32, 4),
])
def test_slots_matches_xla_lowering(F, N, C, B, K):
    rng = np.random.RandomState(F + N + K)
    X = rng.randint(0, B - 1, size=(F, N)).astype(np.uint8)
    vals = _bf16_exact_vals(rng, C, N)
    # slots include inactive rows (slot == -1 and slot == K)
    slot = rng.randint(-1, K + 1, size=N).astype(np.int32)
    ref = _build_histogram_slots_xla(jnp.asarray(X), jnp.asarray(vals),
                                     jnp.asarray(slot), K, B)
    got = build_histogram_slots_pallas(jnp.asarray(X), jnp.asarray(vals),
                                       jnp.asarray(slot), K, B,
                                       interpret=True)
    assert got.shape == (K, C, F, B)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=0, atol=1e-6)


def test_bf16_error_bounded_for_continuous_values():
    rng = np.random.RandomState(0)
    F, N, C, B = 4, 8192, 3, 64
    X = rng.randint(0, B - 1, size=(F, N)).astype(np.uint8)
    vals = rng.normal(size=(C, N)).astype(np.float32)
    ref = np.asarray(_build_histogram_xla(jnp.asarray(X), jnp.asarray(vals),
                                          B))
    got = np.asarray(build_histogram_pallas(jnp.asarray(X),
                                            jnp.asarray(vals), B,
                                            interpret=True))
    # bf16 rounds each addend to 8 mantissa bits; bound the bin error by
    # 2^-8 times the sum of absolute addends in that bin
    abs_ref = np.asarray(_build_histogram_xla(
        jnp.asarray(X), jnp.asarray(np.abs(vals)), B))
    err_bound = abs_ref * 2.0 ** -8 + 1e-6
    assert np.all(np.abs(got - ref) <= err_bound)


def test_masked_rows_contribute_nothing():
    rng = np.random.RandomState(0)
    F, N, C, B = 4, 512, 3, 32
    X = rng.randint(0, 31, size=(F, N)).astype(np.uint8)
    vals = _bf16_exact_vals(rng, C, N)
    mask = (rng.rand(N) < 0.5).astype(np.float32)
    vals_masked = vals * mask[None, :]
    got = build_histogram_pallas(jnp.asarray(X), jnp.asarray(vals_masked), B,
                                 interpret=True)
    ref = _build_histogram_xla(jnp.asarray(X[:, mask > 0]),
                               jnp.asarray(vals[:, mask > 0]), B)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# Bin-width-tiered path (ops/histogram_tiered.py, docs/PERF.md): per-class
# kernels into a flat per-feature-offset buffer, expanded back to the
# uniform grid — parity with the XLA reference and BITWISE identity with
# the legacy uniform kernel (the acceptance contract: each feature's sum
# runs over the same rows in the same row-block order).
# ---------------------------------------------------------------------------

MIXED_NBINS = (15, 15, 63, 63, 63, 255, 255, 30, 120)


def _tiered_inputs(nbins, N, rng):
    X = np.stack([rng.randint(0, nb, N) for nb in nbins]).astype(np.uint8)
    return X


@pytest.mark.parametrize("nbins,B", [
    (MIXED_NBINS, 256),               # mixed classes, unsorted tail
    ((15, 9, 4), 16),                 # all-narrow, num_bins = 15-ish
    ((63, 63, 40, 7), 64),            # two classes at 63-bin config
    ((255,) * 5 + (63,) * 4, 256),    # wide + narrow at 255-bin config
])
@pytest.mark.parametrize("hilo", [True, False])
def test_tiered_slots_matches_xla_and_legacy(nbins, B, hilo):
    from lightgbm_tpu.ops.histogram_tiered import (build_tier_plan,
                                                   build_histogram_slots_tiered)
    rng = np.random.RandomState(sum(nbins))
    N, C, K = 1500, 3, 4
    X = _tiered_inputs(nbins, N, rng)
    vals = _bf16_exact_vals(rng, C, N)
    slot = rng.randint(-1, K + 1, size=N).astype(np.int32)
    plan = build_tier_plan(nbins)
    assert plan.total == sum(c * w for (_, c, w) in plan.classes)
    ref = _build_histogram_slots_xla(jnp.asarray(X), jnp.asarray(vals),
                                     jnp.asarray(slot), K, B)
    got = build_histogram_slots_tiered(jnp.asarray(X), jnp.asarray(vals),
                                       jnp.asarray(slot), K, B, plan,
                                       interpret=True, hilo=hilo)
    assert got.shape == (K, C, len(nbins), B)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    leg = build_histogram_slots_pallas(jnp.asarray(X), jnp.asarray(vals),
                                       jnp.asarray(slot), K, B,
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(leg), np.asarray(got))


def test_tiered_quantized_int8_exact():
    from lightgbm_tpu.ops.histogram_tiered import (build_tier_plan,
                                                   build_histogram_slots_tiered)
    rng = np.random.RandomState(21)
    N, K, B = 1200, 4, 256
    X = _tiered_inputs(MIXED_NBINS, N, rng)
    vals = rng.randint(-127, 128, size=(2, N)).astype(np.int8)
    slot = rng.randint(-1, K, size=N).astype(np.int32)
    plan = build_tier_plan(MIXED_NBINS)
    ref = _build_histogram_slots_xla(jnp.asarray(X), jnp.asarray(vals),
                                     jnp.asarray(slot), K, B)
    got = build_histogram_slots_tiered(jnp.asarray(X), jnp.asarray(vals),
                                       jnp.asarray(slot), K, B, plan,
                                       interpret=True, hilo=True)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_tiered_flat_offsets_agree_with_reference():
    """The ragged flat buffer itself: feature f's columns
    [offset[f], offset[f]+width[f]) hold exactly its reference histogram
    (the FeatureGroupOffsets layout contract)."""
    from lightgbm_tpu.ops.histogram_tiered import (
        build_tier_plan, build_histogram_slots_tiered_flat)
    rng = np.random.RandomState(33)
    N, K, B = 900, 3, 256
    X = _tiered_inputs(MIXED_NBINS, N, rng)
    vals = _bf16_exact_vals(rng, 2, N)
    slot = rng.randint(-1, K, size=N).astype(np.int32)
    plan = build_tier_plan(MIXED_NBINS)
    flat = np.asarray(build_histogram_slots_tiered_flat(
        jnp.asarray(X), jnp.asarray(vals), jnp.asarray(slot), K, plan,
        interpret=True))
    ref = np.asarray(_build_histogram_slots_xla(
        jnp.asarray(X), jnp.asarray(vals), jnp.asarray(slot), K, B))
    for f, nb in enumerate(MIXED_NBINS):
        off, w = plan.offsets[f], plan.widths[f]
        np.testing.assert_array_equal(flat[:, :, off:off + nb],
                                      ref[:, :, f, :nb])
        # columns beyond the feature's bins hold no mass
        assert np.all(flat[:, :, off + nb:off + w] == 0.0)


@pytest.mark.parametrize("num_bins", [15, 63, 255])
def test_tiered_bin_configs(num_bins):
    """num_bins sweep from the ISSUE checklist: single-width datasets at
    each config, K=1 wrapper path."""
    from lightgbm_tpu.ops.histogram_tiered import (build_tier_plan,
                                                   build_histogram_tiered)
    rng = np.random.RandomState(num_bins)
    F, N = 6, 2000
    nbins = (num_bins,) * F
    X = _tiered_inputs(nbins, N, rng)
    vals = _bf16_exact_vals(rng, 2, N)
    plan = build_tier_plan(nbins)
    assert len(plan.classes) == 1
    ref = _build_histogram_xla(jnp.asarray(X), jnp.asarray(vals), num_bins)
    got = build_histogram_tiered(jnp.asarray(X), jnp.asarray(vals),
                                 num_bins, plan, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_hilo_wide_lo_bitwise_identical():
    """The hi/lo wide-bin variant (wide_lo=64, 4 masked narrow matmuls)
    must reproduce the legacy 128-wide two-pass split bit-for-bit — the
    mask is exactly 0/1 in bf16, so every product and f32 sum agrees."""
    rng = np.random.RandomState(44)
    F, N, C, K, B = 12, 3000, 3, 4, 256
    X = rng.randint(0, 255, size=(F, N)).astype(np.uint8)
    vals = _bf16_exact_vals(rng, C, N)
    slot = rng.randint(-1, K, size=N).astype(np.int32)
    h64 = build_histogram_slots_pallas(jnp.asarray(X), jnp.asarray(vals),
                                       jnp.asarray(slot), K, B,
                                       interpret=True, wide_lo=64)
    h128 = build_histogram_slots_pallas(jnp.asarray(X), jnp.asarray(vals),
                                        jnp.asarray(slot), K, B,
                                        interpret=True, wide_lo=128)
    np.testing.assert_array_equal(np.asarray(h64), np.asarray(h128))
    # quantized mode decomposes per-pass too
    q = rng.randint(-64, 64, size=(2, N)).astype(np.int8)
    q64 = build_histogram_slots_pallas(jnp.asarray(X), jnp.asarray(q),
                                       jnp.asarray(slot), K, B,
                                       interpret=True, wide_lo=64)
    q128 = build_histogram_slots_pallas(jnp.asarray(X), jnp.asarray(q),
                                        jnp.asarray(slot), K, B,
                                        interpret=True, wide_lo=128)
    np.testing.assert_array_equal(np.asarray(q64), np.asarray(q128))


def test_tier_route_dispatch():
    """_tier_route contract: legacy pin, feature-slice guard, single- vs
    multi-class routing, and the narrower-than-num_bins single class."""
    from lightgbm_tpu.ops.histogram import _tier_route
    assert _tier_route(MIXED_NBINS, len(MIXED_NBINS), 256, "legacy") is None
    assert _tier_route((), 9, 256, "auto") is None
    assert _tier_route(MIXED_NBINS, 4, 256, "auto") is None   # sliced X
    r = _tier_route(MIXED_NBINS, len(MIXED_NBINS), 256, "auto")
    assert r[0] == "tiered"
    single = _tier_route((255,) * 28, 28, 256, "auto")
    assert single == ("legacy", 256, 64)
    assert _tier_route((255,) * 28, 28, 256, "tiered") == ("legacy", 256,
                                                           128)
    # all-narrow dataset under a wide padded config runs the narrow kernel
    assert _tier_route((40,) * 6, 6, 256, "auto") == ("legacy", 64, 128)


def test_wave_pass_wide_lo_parity():
    """wave_pass_pallas with the hi/lo variant: identical relabel and
    bitwise-identical histograms vs the legacy decomposition."""
    from lightgbm_tpu.ops.histogram_pallas import wave_pass_pallas
    rng = np.random.RandomState(55)
    F, N, B, K = 9, 2000, 256, 8
    X = rng.randint(0, 255, size=(F, N)).astype(np.uint8)
    vals = _bf16_exact_vals(rng, 2, N)
    lor = rng.randint(0, 12, size=N).astype(np.int32)
    tblr = [np.array([0, 3, 5, 7, -1, -1, -1, -1]),
            rng.randint(0, F, size=K), rng.randint(0, B - 2, size=K),
            rng.randint(0, 2, size=K), np.array([MT_NONE] * K),
            rng.randint(0, B - 1, size=K), np.full(K, B - 1),
            np.array([0, 12, 3, 13, 9, 11, -1, -1]),
            rng.randint(0, F, size=K), rng.randint(0, B - 2, size=K),
            rng.randint(0, 2, size=K), np.array([MT_NONE] * K),
            rng.randint(0, B - 1, size=K), np.full(K, B - 1),
            rng.randint(0, 2, size=K), np.full(K, 12)]
    tbl_np = np.stack([np.asarray(t, np.int32) for t in tblr])
    tbl16 = jnp.asarray(np.pad(tbl_np, ((0, 0), (0, 128 - K)),
                               constant_values=-1))
    lor64, hist64, _ = wave_pass_pallas(jnp.asarray(X), jnp.asarray(vals),
                                     jnp.asarray(lor), tbl16, K, B,
                                     interpret=True, wide_lo=64)
    lor128, hist128, _ = wave_pass_pallas(jnp.asarray(X), jnp.asarray(vals),
                                       jnp.asarray(lor), tbl16, K, B,
                                       interpret=True, wide_lo=128)
    np.testing.assert_array_equal(np.asarray(lor64), np.asarray(lor128))
    np.testing.assert_array_equal(np.asarray(hist64), np.asarray(hist128))


# ---------------------------------------------------------------------------
# Wave megakernel (fused relabel + candidate membership + slot histogram)
# and the leaf-value one-hot gather — interpret-mode parity with numpy
# references implementing the portable-path semantics (grow_wave.py
# table_go_left).
# ---------------------------------------------------------------------------

MT_NONE, MT_ZERO, MT_NAN = 0, 1, 2


def _ref_go_left(col, thr, dleft, mt, db, nb):
    missing = ((mt == MT_ZERO) & (col == db)) | \
              ((mt == MT_NAN) & (col == nb - 1))
    return np.where(missing, dleft, col <= thr)


def _ref_wave_pass(X, vals, lor, tbl, K, B):
    """Numpy reference for _wave_kernel: relabel rows of applied splits,
    then candidate smaller-child membership on the new leaf, then the
    slot histogram."""
    F, N = X.shape
    C = vals.shape[0]
    (a_leaf, a_feat, a_thr, a_dl, a_mt, a_db, a_nb,
     c_leaf, c_feat, c_thr, c_dl, c_mt, c_db, c_nb, c_sil, nl0r) = tbl
    nl0 = nl0r[0]
    new_lor = lor.copy()
    slot_small = np.full(N, -1, np.int64)
    for r in range(N):
        sA = -1
        for j in range(K):
            if a_leaf[j] == lor[r]:
                sA = j
        if sA >= 0:
            col = int(X[a_feat[sA], r])
            gl = _ref_go_left(col, a_thr[sA], a_dl[sA], a_mt[sA],
                              a_db[sA], a_nb[sA])
            if not gl:
                new_lor[r] = nl0 + sA
        sC = -1
        for j in range(K):
            if c_leaf[j] == new_lor[r]:
                sC = j
        if sC >= 0:
            col = int(X[c_feat[sC], r])
            gl = _ref_go_left(col, c_thr[sC], c_dl[sC], c_mt[sC],
                              c_db[sC], c_nb[sC])
            if int(gl) == c_sil[sC]:
                slot_small[r] = sC
    hist = np.zeros((K, C, F, B), np.float64)
    for r in range(N):
        if slot_small[r] >= 0:
            for f in range(F):
                hist[slot_small[r], :, f, X[f, r]] += vals[:, r]
    return new_lor, hist


def test_wave_pass_matches_reference():
    from lightgbm_tpu.ops.histogram_pallas import wave_pass_pallas
    rng = np.random.RandomState(3)
    F, N, B, K = 9, 2000, 64, 8
    X = rng.randint(0, B - 1, size=(F, N)).astype(np.uint8)
    vals = _bf16_exact_vals(rng, 2, N)
    lor = rng.randint(0, 12, size=N).astype(np.int32)

    def slot_tbl(leaves):
        feat = rng.randint(0, F, size=K)
        thr = rng.randint(0, B - 2, size=K)
        dl = rng.randint(0, 2, size=K)
        mt = rng.choice([MT_NONE, MT_ZERO, MT_NAN], size=K)
        db = rng.randint(0, B - 1, size=K)
        nb = np.full(K, B - 1)
        return leaves, feat, thr, dl, mt, db, nb

    app = slot_tbl(np.array([0, 3, 5, 7, -1, -1, -1, -1]))
    # candidates: mix of surviving leaves and fresh right children (12+j)
    cand = slot_tbl(np.array([0, 12, 3, 13, 9, 11, -1, -1]))
    sil = rng.randint(0, 2, size=K)
    nl0 = np.full(K, 12)
    tbl = [*app, *cand, sil, nl0]
    tbl_np = np.stack([np.asarray(t, np.int32) for t in tbl])
    tbl16 = jnp.asarray(np.pad(tbl_np, ((0, 0), (0, 128 - K)), constant_values=-1))

    ref_lor, ref_hist = _ref_wave_pass(X, vals, lor, tbl, K, B)
    got_lor, got_hist, _ = wave_pass_pallas(
        jnp.asarray(X), jnp.asarray(vals), jnp.asarray(lor), tbl16, K, B,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(got_lor), ref_lor)
    np.testing.assert_allclose(np.asarray(got_hist), ref_hist,
                               rtol=0, atol=1e-6)


def test_wave_pass_quantized_int8_exact():
    from lightgbm_tpu.ops.histogram_pallas import wave_pass_pallas
    rng = np.random.RandomState(4)
    F, N, B, K = 5, 1200, 32, 4
    X = rng.randint(0, B - 1, size=(F, N)).astype(np.uint8)
    vals = rng.randint(-127, 128, size=(2, N)).astype(np.int8)
    lor = rng.randint(0, 6, size=N).astype(np.int32)
    app = (np.array([1, 4, -1, -1]), np.array([0, 2, 0, 0]),
           np.array([10, 20, 0, 0]), np.array([0, 1, 0, 0]),
           np.array([MT_NONE] * 4), np.zeros(4, int), np.full(4, B - 1))
    cand = (np.array([1, 6, 4, 7]), np.array([1, 3, 2, 4]),
            np.array([5, 15, 25, 8]), np.array([1, 0, 0, 1]),
            np.array([MT_NONE] * 4), np.zeros(4, int), np.full(4, B - 1))
    sil = np.array([1, 0, 1, 0])
    nl0 = np.full(4, 6)
    tbl = [*app, *cand, sil, nl0]
    tbl_np = np.stack([np.asarray(t, np.int32) for t in tbl])
    tbl16 = jnp.asarray(np.pad(tbl_np, ((0, 0), (0, 128 - K)), constant_values=-1))
    ref_lor, ref_hist = _ref_wave_pass(X, vals.astype(np.int64), lor, tbl,
                                       K, B)
    got_lor, got_hist, _ = wave_pass_pallas(
        jnp.asarray(X), jnp.asarray(vals), jnp.asarray(lor), tbl16, K, B,
        interpret=True)
    assert got_hist.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got_lor), ref_lor)
    np.testing.assert_array_equal(np.asarray(got_hist), ref_hist)


def test_wave_pass_prepadded_inputs():
    """Caller-side pre-padding (F to 32, rows to a block multiple) must
    give identical results to unpadded inputs."""
    from lightgbm_tpu.ops.histogram_pallas import wave_pass_pallas
    rng = np.random.RandomState(5)
    F, N, B, K = 6, 700, 32, 2
    X = rng.randint(0, B - 1, size=(F, N)).astype(np.uint8)
    vals = _bf16_exact_vals(rng, 2, N)
    lor = rng.randint(0, 4, size=N).astype(np.int32)
    tblr = [np.array([0, 2]), np.array([1, 3]), np.array([4, 9]),
            np.array([0, 1]), np.array([MT_NONE] * 2), np.zeros(2, int),
            np.full(2, B - 1),
            np.array([4, 2]), np.array([2, 0]), np.array([7, 3]),
            np.array([1, 0]), np.array([MT_NONE] * 2), np.zeros(2, int),
            np.full(2, B - 1), np.array([1, 0]), np.full(2, 4)]
    tbl_np = np.stack([np.asarray(t, np.int32) for t in tblr])
    tbl16 = jnp.asarray(np.pad(tbl_np, ((0, 0), (0, 126)), constant_values=-1))
    lor_j = jnp.asarray(lor)
    got1 = wave_pass_pallas(jnp.asarray(X), jnp.asarray(vals), lor_j,
                            tbl16, K, B, interpret=True)
    Np = 1024
    Xp = jnp.asarray(np.pad(X.astype(np.int8), ((0, 32 - F), (0, Np - N))))
    vp = jnp.asarray(np.pad(vals, ((0, 0), (0, Np - N))))
    got2 = wave_pass_pallas(Xp, vp, lor_j, tbl16, K, B, interpret=True)
    np.testing.assert_array_equal(np.asarray(got1[0]), np.asarray(got2[0]))
    np.testing.assert_allclose(np.asarray(got1[1]),
                               np.asarray(got2[1][:, :, :F, :]),
                               rtol=0, atol=1e-6)


def test_take_leaf_values_exact():
    from lightgbm_tpu.ops.histogram_pallas import take_leaf_values_pallas
    rng = np.random.RandomState(6)
    for L, N in ((255, 5000), (31, 300), (1024, 2000)):
        vals = rng.normal(size=L).astype(np.float32)
        lor = rng.randint(0, L, size=N).astype(np.int32)
        got = take_leaf_values_pallas(jnp.asarray(vals), jnp.asarray(lor),
                                      interpret=True)
        np.testing.assert_array_equal(np.asarray(got), vals[lor])


def test_wave_apply_matches_reference():
    """wave_apply_pallas (wide/categorical/EFB path): precomputed
    per-(entry, row) decision bits -> relabel + candidate slots."""
    from lightgbm_tpu.ops.histogram_pallas import wave_apply_pallas
    rng = np.random.RandomState(11)
    N, K = 3000, 12
    lor = rng.randint(0, 20, size=N).astype(np.int32)
    app_leaf = np.full(128, -1, np.int32)
    app_leaf[:K] = rng.choice(20, K, replace=False)
    cand_leaf = np.full(128, -1, np.int32)
    cand_leaf[:K] = rng.choice(40, K, replace=False)
    nl0 = 20
    glA = rng.randint(0, 2, size=(128, N))
    small = rng.randint(0, 2, size=(128, N))
    dec = (glA | (small << 1)).astype(np.int8)

    tbl = np.full((16, 128), -1, np.int32)
    tbl[0] = app_leaf
    tbl[7] = cand_leaf
    tbl[15] = nl0

    got_lor, got_slot = wave_apply_pallas(
        jnp.asarray(dec), jnp.asarray(lor), jnp.asarray(tbl),
        interpret=True)

    # numpy reference
    ref_lor = lor.copy()
    for k in range(128):
        if app_leaf[k] < 0:
            continue
        m = (lor == app_leaf[k]) & (glA[k] == 0)
        ref_lor[m] = nl0 + k
    ref_slot = np.full(N, -1, np.int64)
    for k in range(128):
        if cand_leaf[k] < 0:
            continue
        m = (ref_lor == cand_leaf[k]) & (small[k] == 1)
        ref_slot[m] = k
    np.testing.assert_array_equal(np.asarray(got_lor), ref_lor)
    np.testing.assert_array_equal(np.asarray(got_slot), ref_slot)


@pytest.mark.parametrize("wave", [1, 128])
@pytest.mark.parametrize("default_left", [0, 1])
@pytest.mark.parametrize("missing", ["none", "zero", "nan"])
@pytest.mark.parametrize("F", [33, 137, 968])
def test_wave_decide_matches_decision_matrix(F, missing, default_left,
                                             wave):
    """wave_decide_pallas (numeric tables past 32 columns: each row's
    decision made in the kernel) gives, bit for bit, what the matrix
    route gives: the [128, N] go-left bits of grow_wave's dec_go_left
    (its numeric branch, here in numpy) through wave_apply_pallas. Bins
    span the whole byte (int8 storage reads negative from 128 up),
    thresholds include 0 and num_bins - 1, and inactive entries (-1) sit
    among the active ones."""
    from lightgbm_tpu.models.tree import MISSING_NAN, MISSING_ZERO
    from lightgbm_tpu.ops.histogram_pallas import (
        _T_APP_LEAF, _T_CAND_LEAF, _T_CAND_SIL, _T_NL0, wave_apply_pallas,
        wave_decide_pallas)
    rng = np.random.RandomState(F * 7 + wave)
    N, L, K = 5000, 200, 128               # N: 2 row blocks + a tail
    nb = rng.choice([16, 64, 256], size=F).astype(np.int32)
    db = (rng.randint(0, 1 << 30, size=F) % nb).astype(np.int32)
    mt = np.full(F, {"none": 0, "zero": MISSING_ZERO,
                     "nan": MISSING_NAN}[missing], np.int32)
    X = (rng.randint(0, 1 << 30, size=(F, N)) % nb[:, None]) \
        .astype(np.uint8)
    lor = rng.randint(0, L, size=N).astype(np.int32)

    # rows base .. base + 6: leaf, feature, threshold, default-left,
    # missing type, default bin, bin count (histogram_pallas.py layout)
    tbl = np.zeros((16, 128), np.int32)
    for base, leaves in (
            (_T_APP_LEAF, rng.choice(L, K, replace=False)),
            (_T_CAND_LEAF, rng.choice(L + wave, K, replace=False))):
        leaves[wave:] = -1                 # wave entries active ...
        leaves[3:wave:5] = -1              # ... with holes among them
        feat = rng.randint(0, F, size=K)
        thr = np.where(np.arange(K) % 3 == 0, 0,
                       np.where(np.arange(K) % 3 == 1, nb[feat] - 1,
                                rng.randint(0, 1 << 30, size=K) % nb[feat]))
        if wave == 1:                      # applied at 0, candidate at top
            thr[0] = 0 if base == _T_APP_LEAF else nb[feat[0]] - 1
        tbl[base:base + 7] = [leaves, feat, thr, np.full(K, default_left),
                              mt[feat], db[feat], nb[feat]]
    tbl[_T_CAND_SIL] = rng.randint(0, 2, size=K)
    tbl[_T_CAND_SIL, 0] = 1                # threshold nb - 1: left is big
    tbl[_T_NL0] = L

    def bits(base):
        f, thr, dl = tbl[base + 1], tbl[base + 2], tbl[base + 3]
        b = X[f].astype(np.int32)                          # [128, N]
        miss = ((mt[f] == MISSING_ZERO)[:, None] & (b == db[f][:, None])) \
            | ((mt[f] == MISSING_NAN)[:, None] & (b == nb[f][:, None] - 1))
        return np.where(miss, dl[:, None] == 1, b <= thr[:, None])
    small = bits(_T_CAND_LEAF) == (tbl[_T_CAND_SIL] == 1)[:, None]
    dec = (bits(_T_APP_LEAF) | (small << 1)).astype(np.int8)
    t = jnp.asarray(tbl)
    want = wave_apply_pallas(jnp.asarray(dec), jnp.asarray(lor), t,
                             interpret=True)
    got = wave_decide_pallas(jnp.asarray(X), jnp.asarray(lor), t,
                             interpret=True)
    moved = np.asarray(want[0]) != lor
    assert moved.any() and (np.asarray(want[1]) >= 0).any()
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


# ---- the chip's compiler, without the chip -----------------------------
@pytest.fixture(scope="module")
def one_chip():
    """A described v5e (libtpu's compile-only topology); nothing runs."""
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _lower_decide(sharding, F, N):
    import jax
    from lightgbm_tpu.ops.histogram_pallas import wave_decide_pallas
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return jax.jit(lambda X, lor, t: wave_decide_pallas(X, lor, t)).lower(
        S((F, N), jnp.uint8), S((N,), jnp.int32), S((16, 128), jnp.int32))


@pytest.mark.parametrize("F,N", [(137, 13_621_776), (968, 1_000_001),
                                 (4000, 100_000)])
def test_wave_decide_compiles_for_the_chip(one_chip, F, N):
    """The in-kernel decision at the ranking cell's width and rows, at
    bosch968's width, and past F_pad 2048 (where the row block shrinks):
    its VMEM does not grow with F (32-feature tiles), and the binned rows
    are read where they lie, uint8 and unpadded, so the program holds no
    temporary of their size."""
    compiled = _lower_decide(one_chip, F, N).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_kernel_lowering_carries_no_source_location(one_chip):
    """runtime/device.py lowers without Python frames, so one kernel
    lowered from two call sites on different lines gives one text, and
    its Mosaic body names no file: an edit above a kernel's call site, or
    another checkout path, keeps its compile-cache key."""
    import base64
    import re
    a = _lower_decide(one_chip, 40, 5000).as_text()
    b = (lambda: _lower_decide(one_chip, 40, 5000))().as_text()
    assert a == b
    body = base64.b64decode(re.search(
        r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', a).group(1))
    assert b".py" not in body
