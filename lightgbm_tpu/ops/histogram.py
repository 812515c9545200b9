"""Histogram construction on device.

The reference's hot loop #1 (Bin::ConstructHistogram, src/io/dense_bin.hpp /
sparse_bin.hpp; CUDA analog cuda_histogram_constructor.cu:20-72) is a
gather-accumulate: hist[bin[r, f]] += (grad[r], hess[r]).

TPUs have no scatter-add in the VPU/MXU path, so the TPU-native formulation is
a one-hot contraction on the MXU: for each row-chunk,

    hist[c, f, b] += sum_r  onehot(bin[f, r] == b) * vals[c, r]

which XLA lowers to batched [C, R] @ [R, B] matmuls per feature. The fused
Pallas variants live in `histogram_pallas.py`; this module holds the portable
XLA lowerings (CPU test meshes, fallback) and the dispatch.

Layouts (all channel-major — the bin axis rides the 128-lane dimension):
  X_t   [F, N]      int8/uint8, feature-major
  vals  [C, N]      f32 (gradient / hessian / count channels)
  hist  [C, F, B]   f32  (single leaf)   or   [K, C, F, B] (wave of K slots)

`build_histogram_slots` is the wave kernel: `slot` assigns each row to one of
K histogram sets (or none, slot outside [0, K)); one pass over the data
produces all K children's histograms — the per-feature one-hot work is shared
across the whole wave, which is the key TPU-side economy over re-scanning
per split (see ops/grow_wave.py).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp


from ..utils import round_up as _round_up


def pallas_interpret() -> bool:
    """LIGHTGBM_TPU_PALLAS_INTERPRET=1 routes every Pallas histogram /
    wave kernel through the Pallas interpreter (any backend): the
    kernel-true CPU mode the parity suites use
    (tests/test_wave_kernels.py). Read at TRACE time, like the kill
    switch below."""
    return os.environ.get("LIGHTGBM_TPU_PALLAS_INTERPRET", "").lower() \
        in ("1", "true", "yes")


def _use_pallas(X_binned_t: jnp.ndarray, num_bins: int) -> bool:
    """Fused Pallas kernel on real TPU backends; XLA lowering elsewhere
    (CPU test meshes, >8-bit bins).

    The env-var kill switch is read at TRACE time: it must be set before the
    first training step of the process (the jit cache is not keyed on it).
    """
    if os.environ.get("LIGHTGBM_TPU_DISABLE_PALLAS", "").lower() \
            in ("1", "true", "yes"):
        return False
    if num_bins > 256 or X_binned_t.dtype not in (jnp.uint8, jnp.int8):
        return False
    return pallas_interpret() or jax.default_backend() == "tpu"


def _tier_route(tiers, F: int, num_bins: int, impl: str):
    """Decide how a Pallas histogram call runs (docs/PERF.md).

    `tiers` is the per-STORAGE-COLUMN bin count tuple in storage order
    (GrowConfig.hist_tiers); `impl` is one of "auto" / "legacy" /
    "tiered" / "tiered_hilo" / "rowwise" / "rowwise_packed"
    (config.histogram_impl, possibly overridden by runtime/autotune.py).

    Returns None (uniform legacy kernel, caller's num_bins), or
    ("legacy", eff_bins, wide_lo) — single width class: one kernel
    sized to the class lane width (zero-padded back up to num_bins),
    with the hi/lo wide-bin variant when eligible — or
    ("tiered", plan, hilo) for the multi-class flat-offset path, or
    ("rowwise", rplan) for the row-wise multi-value path
    (histogram_rowwise.py; the caller still checks `rowwise_eligible`
    against its C*K output size and falls back to the col-wise route),
    or ("rowwise_packed", rplan, pplan) for its 4-bit packed variant
    (falls back to plain rowwise when fewer than two columns fit a
    nibble).

    The `len(tiers) != F` guard keeps callers that slice the feature
    axis (feature-parallel shards, compile-warm dummy calls) on the
    legacy kernel rather than mis-applying a full-width plan."""
    if impl == "legacy" or not tiers or len(tiers) != F \
            or max(tiers) > 256:
        return None
    if impl in ("rowwise", "rowwise_packed"):
        from .histogram_rowwise import (build_pack4_plan,
                                        build_rowwise_plan,
                                        pack4_worthwhile)
        rplan = build_rowwise_plan(tuple(int(t) for t in tiers))
        if impl == "rowwise_packed":
            pplan = build_pack4_plan(tuple(int(t) for t in tiers))
            if pack4_worthwhile(pplan):
                return ("rowwise_packed", rplan, pplan)
        return ("rowwise", rplan)
    from .histogram_tiered import build_tier_plan, class_wide_lo
    plan = build_tier_plan(tuple(int(t) for t in tiers))
    hilo = impl in ("auto", "tiered_hilo")
    if len(plan.classes) == 1:
        lane_B = plan.classes[0][2]
        eff = min(num_bins, lane_B)
        return ("legacy", eff, class_wide_lo(lane_B, hilo))
    return ("tiered", plan, hilo)


def build_histogram(
    X_binned_t: jnp.ndarray,   # [F, N] uint8/uint16/int32 (feature-major)
    vals: jnp.ndarray,         # [C, N] float32 (already masked for leaf/bag)
    num_bins: int,             # B: padded bin-axis size (static)
    rows_per_chunk: int = 8192,
    dtype=jnp.float32,
    *,
    tiers: tuple = (),
    impl: str = "auto",
) -> jnp.ndarray:
    """Dense one-hot-matmul histogram: returns [C, F, B] float32.

    `vals` must already be masked (zeroed) for rows outside the target leaf /
    bag. `tiers`/`impl` select the bin-width-tiered Pallas path
    (_tier_route); the XLA lowering ignores them (its one-hot is already
    sized by `num_bins` alone, and it is the pinned test reference).
    """
    if _use_pallas(X_binned_t, num_bins):
        from .histogram_pallas import build_histogram_pallas
        interp = pallas_interpret()
        route = _tier_route(tiers, X_binned_t.shape[0], num_bins, impl)
        if route is not None and route[0] in ("rowwise", "rowwise_packed"):
            from .histogram_rowwise import (
                build_histogram_rowwise, build_histogram_slots_rowwise_packed,
                rowwise_eligible)
            if rowwise_eligible(route[1], vals.shape[0], 1):
                if route[0] == "rowwise_packed":
                    slot0 = jnp.zeros((X_binned_t.shape[1],), jnp.int32)
                    return build_histogram_slots_rowwise_packed(
                        X_binned_t, vals, slot0, 1, num_bins,
                        route[1], route[2], interpret=interp)[0]
                return build_histogram_rowwise(X_binned_t, vals, num_bins,
                                               route[1], interpret=interp)
            # flat output exceeds the VMEM residency budget: col-wise
            route = _tier_route(tiers, X_binned_t.shape[0], num_bins,
                                "auto")
        if route is None:
            return build_histogram_pallas(X_binned_t, vals, num_bins,
                                          interpret=interp)
        if route[0] == "legacy":
            _, eff, wide_lo = route
            h = build_histogram_pallas(X_binned_t, vals, eff,
                                       wide_lo=wide_lo, interpret=interp)
            if eff < num_bins:
                h = jnp.pad(h, ((0, 0), (0, 0), (0, num_bins - eff)))
            return h
        from .histogram_tiered import build_histogram_tiered
        _, plan, hilo = route
        return build_histogram_tiered(X_binned_t, vals, num_bins, plan,
                                      hilo=hilo, interpret=interp)
    return _build_histogram_xla(X_binned_t, vals, num_bins, rows_per_chunk,
                                dtype)


def build_histogram_slots(
    X_binned_t: jnp.ndarray,   # [F, N] uint8/int8 (feature-major)
    vals: jnp.ndarray,         # [C, N] float32 (bag-masked, NOT slot-masked)
    slot: jnp.ndarray,         # [N] int32: wave slot per row; outside [0, K)
                               #     = row contributes nowhere
    num_slots: int,            # K (static)
    num_bins: int,             # B (static)
    rows_per_chunk: int = 8192,
    *,
    tiers: tuple = (),
    impl: str = "auto",
) -> jnp.ndarray:
    """Wave histogram: returns [K, C, F, B] float32.

    `tiers`/`impl` select the bin-width-tiered Pallas path exactly as in
    `build_histogram` (docs/PERF.md)."""
    if _use_pallas(X_binned_t, num_bins):
        from .histogram_pallas import build_histogram_slots_pallas
        interp = pallas_interpret()
        route = _tier_route(tiers, X_binned_t.shape[0], num_bins, impl)
        if route is not None and route[0] in ("rowwise", "rowwise_packed"):
            from .histogram_rowwise import (
                build_histogram_slots_rowwise,
                build_histogram_slots_rowwise_packed, rowwise_eligible)
            if rowwise_eligible(route[1], vals.shape[0], num_slots):
                if route[0] == "rowwise_packed":
                    return build_histogram_slots_rowwise_packed(
                        X_binned_t, vals, slot, num_slots, num_bins,
                        route[1], route[2], interpret=interp)
                return build_histogram_slots_rowwise(
                    X_binned_t, vals, slot, num_slots, num_bins, route[1],
                    interpret=interp)
            # wide wave: flat output exceeds the VMEM residency budget
            route = _tier_route(tiers, X_binned_t.shape[0], num_bins,
                                "auto")
        if route is None:
            return build_histogram_slots_pallas(X_binned_t, vals, slot,
                                                num_slots, num_bins,
                                                interpret=interp)
        if route[0] == "legacy":
            _, eff, wide_lo = route
            h = build_histogram_slots_pallas(X_binned_t, vals, slot,
                                             num_slots, eff,
                                             wide_lo=wide_lo,
                                             interpret=interp)
            if eff < num_bins:
                h = jnp.pad(h, ((0, 0), (0, 0), (0, 0),
                                (0, num_bins - eff)))
            return h
        from .histogram_tiered import build_histogram_slots_tiered
        _, plan, hilo = route
        return build_histogram_slots_tiered(X_binned_t, vals, slot,
                                            num_slots, num_bins, plan,
                                            hilo=hilo, interpret=interp)
    return _build_histogram_slots_xla(X_binned_t, vals, slot, num_slots,
                                      num_bins, rows_per_chunk)


def take_leaf_values(values: jnp.ndarray,
                     leaf_of_row: jnp.ndarray) -> jnp.ndarray:
    """values[leaf_of_row] with the small-table gather replaced by an
    exact one-hot contraction on TPU (ScoreUpdater::AddScore semantics,
    score_updater.hpp:22 — the reference walks the partition; XLA's
    native gather here runs ~50x below HBM speed). Honors the
    LIGHTGBM_TPU_DISABLE_PALLAS kill switch like every Pallas kernel."""
    if os.environ.get("LIGHTGBM_TPU_DISABLE_PALLAS", "").lower() \
            in ("1", "true", "yes"):
        return values[leaf_of_row]
    if jax.default_backend() == "tpu" and values.ndim == 1 \
            and values.shape[0] <= 2048:
        from .histogram_pallas import take_leaf_values_pallas
        return take_leaf_values_pallas(values, leaf_of_row)
    return values[leaf_of_row]


def _build_histogram_xla(X_binned_t, vals, num_bins, rows_per_chunk=8192,
                         dtype=jnp.float32):
    """Portable XLA lowering (also the pinned reference in kernel tests).
    int8 `vals` accumulate exactly in int32 (quantized-gradient mode)."""
    F, N = X_binned_t.shape
    C = vals.shape[0]
    B = num_bins
    if vals.dtype == jnp.int8:
        dtype = jnp.int32
    acc = jnp.int32 if dtype == jnp.int32 else jnp.float32
    chunk = min(rows_per_chunk, _round_up(N, 128))
    Np = _round_up(N, chunk)
    if Np != N:
        X_binned_t = jnp.pad(X_binned_t, ((0, 0), (0, Np - N)))
        vals = jnp.pad(vals, ((0, 0), (0, Np - N)))
    n_chunks = Np // chunk

    Xc = X_binned_t.reshape(F, n_chunks, chunk).transpose(1, 0, 2)  # [nc,F,R]
    Vc = vals.reshape(C, n_chunks, chunk).transpose(1, 0, 2)        # [nc,C,R]
    iota = jnp.arange(B, dtype=jnp.int32)

    def body(hist, xs):
        xb, vb = xs                                   # [F, R], [C, R]
        onehot = (xb[:, :, None].astype(jnp.int32) == iota[None, None, :]
                  ).astype(dtype)                     # [F, R, B]
        part = jnp.einsum("frb,cr->cfb", onehot, vb.astype(dtype),
                          preferred_element_type=acc)
        return hist + part, None

    hist0 = jnp.zeros((C, F, B), dtype=acc)
    hist, _ = jax.lax.scan(body, hist0, (Xc, Vc))
    return hist


def _build_histogram_slots_xla(X_binned_t, vals, slot, num_slots, num_bins,
                               rows_per_chunk=8192):
    """Portable XLA wave lowering: one-hot over the combined (slot, bin)
    index — the pinned reference for the Pallas wave kernel tests.
    int8 `vals` accumulate exactly in int32 (quantized-gradient mode)."""
    F, N = X_binned_t.shape
    C = vals.shape[0]
    K, B = num_slots, num_bins
    quantized = vals.dtype == jnp.int8
    acc = jnp.int32 if quantized else jnp.float32
    chunk = min(rows_per_chunk, _round_up(N, 128))
    Np = _round_up(N, chunk)
    if Np != N:
        X_binned_t = jnp.pad(X_binned_t, ((0, 0), (0, Np - N)))
        vals = jnp.pad(vals, ((0, 0), (0, Np - N)))
        slot = jnp.pad(slot, (0, Np - N), constant_values=-1)
    n_chunks = Np // chunk

    Xc = X_binned_t.reshape(F, n_chunks, chunk).transpose(1, 0, 2)
    Vc = vals.reshape(C, n_chunks, chunk).transpose(1, 0, 2)
    Sc = slot.reshape(n_chunks, chunk)
    iota_b = jnp.arange(B, dtype=jnp.int32)
    iota_k = jnp.arange(K, dtype=jnp.int32)

    def body(hist, xs):
        xb, vb, sb = xs                               # [F,R], [C,R], [R]
        oh_bin = (xb[:, :, None].astype(jnp.int32) == iota_b[None, None, :]
                  ).astype(acc)                       # [F, R, B]
        oh_slot = (sb[None, :] == iota_k[:, None]).astype(acc)
        w = oh_slot[:, None, :] * vb[None, :, :].astype(acc)  # [K, C, R]
        part = jnp.einsum("frb,kcr->kcfb", oh_bin, w,
                          preferred_element_type=acc)
        return hist + part, None

    hist0 = jnp.zeros((K, C, F, B), acc)
    hist, _ = jax.lax.scan(body, hist0, (Xc, Vc, Sc))
    return hist
