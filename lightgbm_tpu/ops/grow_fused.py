"""Fused histogram + best-split-scan wave megakernel.

Extends the wave megakernel (histogram_pallas._wave_kernel: relabel +
candidate membership + slot histogram) with the cumulative best-split scan
of ops/split.py run IN the same kernel, on the VMEM-resident flat histogram
block, before anything is written back to HBM. Per wave this removes the
full [K, C, F, B] histogram round-trip between the histogram launch and the
XLA split search — the only [N]-sized traffic left is the row stream the
grid already double-buffers (each block's X/vals/lor DMA overlaps the
previous block's compute; Pallas pipelines streamed BlockSpecs
automatically, docs/PERF.md "Fused wave pass").

The scan epilogue runs once, on the final grid step, and traces the ACTUAL
search code — split.synth_count_channel and split.find_best_split — on
values read back out of the output ref:

  * per candidate k the smaller-child histogram is re-assembled from the
    flat [HB*C*K, Fh*LO] layout by HB*C dynamic row loads (no [K,...]
    second copy in VMEM),
  * the parent histogram arrives as a streamed [K, C*F*B] operand held
    VMEM-resident (constant index map) — the large sibling is
    parent - small, exactly the subtraction the unfused path does in XLA,
  * per-child parent scalars (sum_g/sum_h/count/output + smaller_is_left)
    arrive through SMEM and are picked with dynamic scalar reads,
  * the 12 SplitResult fields of each of the 2K children land in one
    [16, RECW] f32 record block via a where-select against a lane iota
    (select, not multiply-accumulate: a -inf gain times a 0.0 one-hot
    would poison the lane with NaN).

Because the scan IS the library search traced on identical inputs in
identical order, the records are bit-identical to the two-pass path by
construction (tests/test_grow_fused.py). The kernel still emits the full
histogram block: the grower caches the smaller-child histograms for the
parent-minus-sibling reuse on the NEXT wave, so the write-back is load-
bearing, not a debug tap — what the fusion removes is the second read.

Two kernels share this machinery:

  wave_pass_fused_pallas        the narrow (F <= 32, float, unconstrained)
                                original — in-kernel relabel + membership
                                + histogram + scan, one launch per wave
  wave_pass_fused_tiled_pallas  the feature-TILED generalization: grid
                                (feature_tiles, N_blocks) with per-tile
                                VMEM accumulators and per-tile scan
                                records merged by a cross-tile argmax in
                                XLA (merge_tile_records). Membership
                                comes from a precomputed [128, N]
                                decision-bit stream (the wave_apply
                                layout), which makes the kernel
                                independent of feature count, EFB
                                unpacking, and categorical bitsets; the
                                in-kernel scan additionally handles
                                quantized int8->int32 accumulators
                                (descaled exactly AFTER the int32
                                parent-minus-sibling subtraction, the
                                order the two-pass path uses), per-child
                                monotone-`basic` bounds via SMEM, and
                                per-child interaction/column masks.

Cross-tile merge invariant: each tile's scan records carry the RAW
(pre-shift) argmax gain in record row 12; the merge minimizes the exact
(raw gain desc, d-major flat index asc) key the two-pass global argmax
orders by, so the merged record is bit-identical to an untiled search.

Gating (grow_wave.py fused_veto_reasons): the fused paths are selected
via histogram_impl="fused" (config pin or autotune win); regimes no
kernel covers (EFB bundles, distribution, forced splits, CEGB,
per-node sampling, extra_trees, monotone "intermediate"/penalty) fall
back to the two-pass megakernel unchanged and record their veto reason
in the training profile extras.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import kernel_name, round_up as _round_up
from .histogram_pallas import (N_BLK, _compute_dims, _feat_chunk,
                               _hist_chunks, _make_W, _pack_wave_table,
                               _T_NL0, _unflatten_hist, _wave_logic)
from .split import (FeatureMeta, SplitHyperParams, find_best_split,
                    synth_count_channel)

# record block rows (f32; int fields are small exact integers in f32 and
# are cast back outside) — first 12 rows follow SplitResult field order
REC_ROWS = 16


def rec_width(kmax: int) -> int:
    """Lane width of the [REC_ROWS, RECW] record block: left children at
    columns [0, kmax), right children at [kmax, 2*kmax)."""
    return _round_up(2 * kmax, 128)


def pack_fused_meta(num_bins, missing_type, default_bin, is_categorical,
                    feature_mask=None) -> jnp.ndarray:
    """[8, 128] i32 per-feature operand for the in-kernel search: rows
    0..3 are the FeatureMeta arrays, row 4 the column-sampling mask
    (all-ones when None — find_best_split treats a full mask and None
    identically)."""
    F = num_bins.shape[0]
    m = jnp.zeros((8, 128), jnp.int32)
    m = m.at[0, :F].set(num_bins.astype(jnp.int32))
    m = m.at[1, :F].set(missing_type.astype(jnp.int32))
    m = m.at[2, :F].set(default_bin.astype(jnp.int32))
    m = m.at[3, :F].set(is_categorical.astype(jnp.int32))
    fm = (jnp.ones((F,), jnp.int32) if feature_mask is None
          else feature_mask.astype(jnp.int32))
    return m.at[4, :F].set(fm)


def pack_fused_scalars(bs, smaller_is_left, kmax: int,
                       leaf_min_lr=None, leaf_max_lr=None,
                       grad_scale=None, hess_scale=None) -> jnp.ndarray:
    """[8, 2*kmax] f32 SMEM operand: per-child parent statistics in the
    record column layout (left block then right block). Row 4 carries
    smaller_is_left duplicated into both halves so the kernel reads it at
    the child's own column. Rows 5/6 hold the per-child monotone-`basic`
    output bounds (-inf/+inf when unconstrained — jnp.clip against them
    is a bitwise no-op); row 7 columns 0/1 hold the quantized-gradient
    descale factors (tiled kernel only)."""
    sil = smaller_is_left.astype(jnp.float32)
    n2 = 2 * kmax
    if leaf_min_lr is None:
        leaf_min_lr = jnp.full((n2,), -jnp.inf, jnp.float32)
    if leaf_max_lr is None:
        leaf_max_lr = jnp.full((n2,), jnp.inf, jnp.float32)
    scales = jnp.zeros((n2,), jnp.float32)
    if grad_scale is not None:
        scales = scales.at[0].set(jnp.asarray(grad_scale, jnp.float32))
        scales = scales.at[1].set(jnp.asarray(hess_scale, jnp.float32))
    rows = [
        jnp.concatenate([bs.left_sum_g, bs.right_sum_g]),
        jnp.concatenate([bs.left_sum_h, bs.right_sum_h]),
        jnp.concatenate([bs.left_count.astype(jnp.float32),
                         bs.right_count.astype(jnp.float32)]),
        jnp.concatenate([bs.left_output, bs.right_output]),
        jnp.concatenate([sil, sil]),
        leaf_min_lr.astype(jnp.float32),
        leaf_max_lr.astype(jnp.float32),
        scales,
    ]
    return jnp.stack(rows).astype(jnp.float32)


def pack_fused_meta_tiled(num_bins, missing_type, default_bin,
                          is_categorical, monotone, tile: int
                          ) -> jnp.ndarray:
    """[8, FT*128] i32 per-feature operand for the TILED in-kernel
    search: tile ft's features live in columns [ft*128, ft*128+tile)
    (128-lane stride regardless of tile width so every tile block is
    lane-aligned). Rows 0..3 are the FeatureMeta arrays, row 4 the
    monotone direction (-1/0/+1; zeros — a bitwise no-op in the scan —
    when unconstrained). Features past F keep num_bins 0, which the
    search maps to gain -inf everywhere."""
    F = num_bins.shape[0]
    ft_n = -(-F // tile)
    fpad = ft_n * tile
    mono = (jnp.zeros((F,), jnp.int32) if monotone is None
            else monotone.astype(jnp.int32))
    m = jnp.zeros((8, fpad), jnp.int32)
    m = m.at[0, :F].set(num_bins.astype(jnp.int32))
    m = m.at[1, :F].set(missing_type.astype(jnp.int32))
    m = m.at[2, :F].set(default_bin.astype(jnp.int32))
    m = m.at[3, :F].set(is_categorical.astype(jnp.int32))
    m = m.at[4, :F].set(mono)
    out = jnp.zeros((8, ft_n, 128), jnp.int32)
    out = out.at[:, :, :tile].set(m.reshape(8, ft_n, tile))
    return out.reshape(8, ft_n * 128)


def fmask_rows(kmax: int) -> int:
    """Sublane-padded row count of the per-child feature-mask operand."""
    return _round_up(2 * kmax, 8)


def pack_fused_fmask_tiled(fm_children: jnp.ndarray, tile: int,
                           kmax: int) -> jnp.ndarray:
    """[fmask_rows(kmax), FT*128] i32 per-child feature masks in the
    record column layout (row col = child col; tile ft's features at
    columns [ft*128, ft*128+tile), like pack_fused_meta_tiled).
    `fm_children` is [2*kmax, F] bool (all-true rows when the child is
    unmasked — find_best_split treats a full mask and None
    identically)."""
    n2, F = fm_children.shape
    assert n2 == 2 * kmax, (n2, kmax)
    ft_n = -(-F // tile)
    fpad = ft_n * tile
    rows = fmask_rows(kmax)
    fm = jnp.zeros((rows, fpad), jnp.int32)
    fm = fm.at[:n2, :F].set(fm_children.astype(jnp.int32))
    out = jnp.zeros((rows, ft_n, 128), jnp.int32)
    out = out.at[:, :, :tile].set(fm.reshape(rows, ft_n, tile))
    return out.reshape(rows, ft_n * 128)


def _fused_scan(out_ref, parent_ref, scal_ref, meta_ref, rec_ref, *,
                K, C, LO, HB, F, Fh, B, KMAX, RECW, hp):
    """Best-split scan over the 2K children of the wave's K candidates,
    reading the smaller-child histograms straight out of the VMEM-resident
    out_ref. Runs on the final grid step only."""
    meta_i = meta_ref[...]                                  # [8, 128] i32
    meta_k = FeatureMeta(
        num_bins=meta_i[0, :F],
        missing_type=meta_i[1, :F],
        default_bin=meta_i[2, :F],
        is_categorical=meta_i[3, :F] != 0,
    )
    fmask = meta_i[4, :F] != 0
    lane = jax.lax.broadcasted_iota(jnp.int32, (REC_ROWS, RECW), 1)

    def child(j, carry):
        k = jnp.where(j < K, j, j - K)
        is_left = j < K
        col = jnp.where(is_left, k, KMAX + k)
        # smaller-child histogram of candidate k from the flat layout
        # (row hb*C*K + c*K + k holds feature-major LO-wide lo-bins of
        # hi-block hb, channel c) — HB*C single-row loads, then the same
        # unflatten _unflatten_hist does outside, minus the K axis
        rows = [out_ref[pl.ds(hb * C * K + c * K + k, 1), :]
                for hb in range(HB) for c in range(C)]      # [1, Fh*LO]
        sm = jnp.concatenate(rows, axis=0).reshape(HB, C, Fh, LO)
        sm = sm.transpose(1, 2, 0, 3).reshape(C, Fh, HB * LO)[:, :F, :B]
        par = parent_ref[pl.ds(k, 1), :].reshape(C, F, B)
        sil = scal_ref[4, col] != 0.0
        # the left child holds the small histogram iff smaller_is_left
        use_small = is_left == sil
        ch = jnp.where(use_small, sm, par - sm)             # [C, F, B]
        sg = scal_ref[0, col]
        sh = scal_ref[1, col]
        cnt = scal_ref[2, col]
        pout = scal_ref[3, col]
        hist3 = synth_count_channel(ch, cnt, sh)
        res = find_best_split(hist3, sg, sh, cnt, pout, meta_k, hp, fmask)
        f32 = jnp.float32
        vals = jnp.stack([
            res.gain.astype(f32),
            res.feature.astype(f32),
            res.threshold.astype(f32),
            res.default_left.astype(f32),
            res.left_sum_g.astype(f32), res.left_sum_h.astype(f32),
            res.left_count.astype(f32),
            res.right_sum_g.astype(f32), res.right_sum_h.astype(f32),
            res.right_count.astype(f32),
            res.left_output.astype(f32), res.right_output.astype(f32),
            jnp.float32(0.0), jnp.float32(0.0),
            jnp.float32(0.0), jnp.float32(0.0),
        ])                                                  # [16]
        return jnp.where(lane == col, vals[:, None], carry)

    rec = jax.lax.fori_loop(0, 2 * K, child,
                            jnp.zeros((REC_ROWS, RECW), jnp.float32))
    rec_ref[...] = rec


def _fused_wave_kernel(x_ref, v_ref, lor_ref, tbl_ref, parent_ref,
                       meta_ref, scal_ref, nl0_ref, newlor_ref, out_ref,
                       rec_ref, *, K, C, LO, HB, F, Fc, Fh, B, KMAX,
                       RECW, hp, n_blocks):
    """Grid (N_blocks,). Same streaming body as _wave_kernel, plus the
    split-scan epilogue on the last step. parent_ref [K, C*F*B] f32,
    meta_ref [8, 128] i32 and rec_ref [REC_ROWS, RECW] f32 use constant
    index maps (VMEM-resident across the whole grid); scal_ref
    [8, 2*KMAX] f32 lives in SMEM for dynamic scalar reads."""
    n = pl.program_id(0)

    @pl.when(n == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    oh_small = _wave_logic(x_ref, v_ref, lor_ref, tbl_ref, nl0_ref,
                           newlor_ref, K=K, C=C, F=F, HB=HB,
                           quantized=False, with_hist=True)

    W = _make_W(v_ref[...], oh_small, C, K, False)
    xx_all = x_ref[0:F, :].astype(jnp.int32)
    if HB > 1:
        xx_all = xx_all & 0xFF
    _hist_chunks(xx_all, W, out_ref, Fc, C=C, K=K, LO=LO, HB=HB,
                 quantized=False)

    @pl.when(n == n_blocks - 1)
    def _():
        _fused_scan(out_ref, parent_ref, scal_ref, meta_ref, rec_ref,
                    K=K, C=C, LO=LO, HB=HB, F=F, Fh=Fh, B=B, KMAX=KMAX,
                    RECW=RECW, hp=hp)


@functools.partial(jax.jit,
                   static_argnames=("num_slots", "num_bins", "kmax", "hp",
                                    "interpret", "wide_lo"))
def wave_pass_fused_pallas(
    X_binned_t: jnp.ndarray,   # [F, N] int8/uint8 (feature-major, F <= 32)
    vals: jnp.ndarray,         # [C, N] f32 (bag-masked)
    leaf_of_row: jnp.ndarray,  # [N] int32
    table: jnp.ndarray,        # [T_ROWS, 128] int32 semantic wave table
    parent_hist: jnp.ndarray,  # [kmax, C*F*B] f32 candidate parent hists
    scal: jnp.ndarray,         # [8, 2*kmax] f32 (pack_fused_scalars)
    meta_ops: jnp.ndarray,     # [8, 128] i32 (pack_fused_meta)
    num_slots: int,
    num_bins: int,
    kmax: int,
    hp: SplitHyperParams,
    interpret: bool = False,
    wide_lo: int = 128,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Single-pass fused wave: returns (new_leaf_of_row [N] i32,
    hist [K, C, F, num_bins] f32, rec [REC_ROWS, RECW] f32).

    rec columns [0, K) and [kmax, kmax+K) hold the left/right children's
    SplitResult fields (rows 0..11 in field order); columns of candidates
    past the wave's bucket K are zero and must be discarded by the
    caller's validity mask (grow_wave scat does). X/vals may be pre-padded
    exactly as for wave_pass_pallas."""
    F, NX = X_binned_t.shape
    C = vals.shape[0]
    N = leaf_of_row.shape[0]
    K = num_slots
    B_lane, LO, HB = _compute_dims(num_bins, wide_lo)
    assert F <= 32, "fused wave kernel requires F <= 32 storage columns"
    assert vals.dtype != jnp.int8, "fused wave kernel is float-mode only"
    Fp = 32
    rows = HB * C * K
    Fc = _feat_chunk(F, LO, rows)
    Fh = _round_up(F, Fc)
    RECW = rec_width(kmax)
    n_blk = N_BLK if NX >= N_BLK else max(_round_up(NX, 256), 256)
    Np = _round_up(NX, n_blk)

    X = X_binned_t.astype(jnp.int8)
    if Fp != F or Np != NX:
        X = jnp.pad(X, ((0, Fp - F), (0, Np - NX)))
    v = vals.astype(jnp.float32)
    if v.shape[1] != Np:
        v = jnp.pad(v, ((0, 0), (0, Np - v.shape[1])))
    lor = leaf_of_row.astype(jnp.int32)
    if Np != N:
        lor = jnp.pad(lor, (0, Np - N), constant_values=-1)
    tblp = _pack_wave_table(table)
    nl0 = table[_T_NL0, 0:1].astype(jnp.int32)
    parent = parent_hist.astype(jnp.float32)[:K]            # [K, C*F*B]
    CFB = C * F * num_bins
    assert parent.shape[1] == CFB, (parent.shape, (K, CFB))

    n_blocks = Np // n_blk
    kernel = functools.partial(_fused_wave_kernel, K=K, C=C, LO=LO, HB=HB,
                               F=F, Fc=Fc, Fh=Fh, B=num_bins, KMAX=kmax,
                               RECW=RECW, hp=hp, n_blocks=n_blocks)
    newlor, out, rec = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((Fp, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((128, 8), lambda n: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((K, CFB), lambda n: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((8, 128), lambda n: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, Fh * LO), lambda n: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((REC_ROWS, RECW), lambda n: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Np), jnp.int32),
            jax.ShapeDtypeStruct((rows, Fh * LO), jnp.float32),
            jax.ShapeDtypeStruct((REC_ROWS, RECW), jnp.float32),
        ],
        name=kernel_name("fused_wave", k=K, b=num_bins),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            # streamed contraction + one scan's cumsums over 2K children
            flops=2 * K * C * Fh * Np * B_lane + 2 * K * 3 * F * B_lane * 8,
            bytes_accessed=Fp * Np + (C * 4 + 8) * Np
            + rows * Fh * LO * 4 + K * CFB * 4,
            transcendentals=0,
        ),
    )(X, v, lor[None, :], tblp, parent, meta_ops, scal, nl0)

    hist = _unflatten_hist(out, K, C, F, Fh, LO, HB, num_bins)
    return newlor[0, :N], hist, rec


def _fused_scan_tiled(out_ref, parent_ref, scal_ref, meta_ref, fm_ref,
                      rec_ref, foff, *, K, C, LO, HB, T, Th, B, KMAX,
                      RECW, hp, quantized):
    """Per-TILE best-split scan: identical to _fused_scan over this
    tile's T features, plus (a) per-child monotone bounds and feature
    masks, (b) exact int32->f32 descale for quantized accumulators
    (AFTER the integer parent-minus-sibling subtraction — the two-pass
    order; c*(a-b) != c*a - c*b in f32), (c) the raw argmax gain in
    record row 12 and the GLOBAL feature id (local + foff) in row 1, the
    two inputs of the cross-tile merge."""
    meta_i = meta_ref[...]                                  # [8, 128] i32
    meta_k = FeatureMeta(
        num_bins=meta_i[0, :T],
        missing_type=meta_i[1, :T],
        default_bin=meta_i[2, :T],
        is_categorical=meta_i[3, :T] != 0,
        monotone=meta_i[4, :T],
    )
    lane = jax.lax.broadcasted_iota(jnp.int32, (REC_ROWS, RECW), 1)
    f32 = jnp.float32

    def child(j, carry):
        k = jnp.where(j < K, j, j - K)
        is_left = j < K
        col = jnp.where(is_left, k, KMAX + k)
        rows = [out_ref[pl.ds(hb * C * K + c * K + k, 1), :]
                for hb in range(HB) for c in range(C)]      # [1, Th*LO]
        sm = jnp.concatenate(rows, axis=0).reshape(HB, C, Th, LO)
        sm = sm.transpose(1, 2, 0, 3).reshape(C, Th, HB * LO)[:, :T, :B]
        par = parent_ref[pl.ds(k, 1), :].reshape(C, T, B)
        sil = scal_ref[4, col] != 0.0
        use_small = is_left == sil
        ch = jnp.where(use_small, sm, par - sm)             # [C, T, B]
        if quantized:
            scale = jnp.stack([scal_ref[7, 0], scal_ref[7, 1]])
            ch = ch.astype(f32) * scale[:, None, None]
        sg = scal_ref[0, col]
        sh = scal_ref[1, col]
        cnt = scal_ref[2, col]
        pout = scal_ref[3, col]
        bmin = scal_ref[5, col]
        bmax = scal_ref[6, col]
        fm = fm_ref[pl.ds(col, 1), :][0, :T] != 0
        hist3 = synth_count_channel(ch, cnt, sh)
        res, raw = find_best_split(hist3, sg, sh, cnt, pout, meta_k, hp,
                                   fm, leaf_min=bmin, leaf_max=bmax,
                                   with_raw=True)
        vals = jnp.stack([
            res.gain.astype(f32),
            (res.feature + foff).astype(f32),
            res.threshold.astype(f32),
            res.default_left.astype(f32),
            res.left_sum_g.astype(f32), res.left_sum_h.astype(f32),
            res.left_count.astype(f32),
            res.right_sum_g.astype(f32), res.right_sum_h.astype(f32),
            res.right_count.astype(f32),
            res.left_output.astype(f32), res.right_output.astype(f32),
            raw.astype(f32),
            jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0),
        ])                                                  # [16]
        return jnp.where(lane == col, vals[:, None], carry)

    rec = jax.lax.fori_loop(0, 2 * K, child,
                            jnp.zeros((REC_ROWS, RECW), jnp.float32))
    rec_ref[...] = rec


def _fused_tiled_kernel(x_ref, v_ref, dec_ref, lor_ref, tbl_ref,
                        parent_ref, meta_ref, fm_ref, scal_ref, nl0_ref,
                        newlor_ref, out_ref, rec_ref, *, K, C, LO, HB, T,
                        Fc, Th, B, KMAX, RECW, hp, quantized, n_blocks):
    """Grid (F_tiles, N_blocks), N fastest (out/rec/parent blocks stay
    VMEM-resident across each tile's row sweep). Membership comes from
    the precomputed [128, R] decision-bit stream (the wave_apply layout:
    bit0 = apply go-left, bit1 = lands in candidate's smaller child), so
    the kernel needs no per-feature column extraction — which is what
    frees it from the F <= 32 / categorical / EFB limits of the in-kernel
    go_left. The relabel is recomputed identically per tile (newlor's
    block revisits write the same value).

    Relabel fusion: a PREVIOUS applies-only wave's deferred RELABEL rides
    in as table column 1 (its applied leaf ids) + decision bit2, applied
    as an extra membership pass BEFORE this wave's own table — folding
    what would have been a standalone relabel launch into this kernel's
    row-ingest prologue. nl0_ref is [2] SMEM: [this wave's first new leaf
    id, the pending wave's]. An empty pending table (all -1) is a no-op:
    no active row matches, and -1 pad rows match every inactive entry at
    once (inP != 1)."""
    ft = pl.program_id(0)
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    R = lor_ref.shape[1]
    dec = dec_ref[...].astype(jnp.int32)                   # [128, R]
    lor = lor_ref[0, :]
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (128, R), 0)

    # pending (deferred) relabel from the previous applies-only wave
    mP = lor[None, :] == tbl_ref[:, 1:2]                   # [128, R]
    glP = jnp.sum(jnp.where(mP, (dec >> 2) & 1, 0), axis=0)
    inP = jnp.sum(jnp.where(mP, 1, 0), axis=0)
    slotP = jnp.sum(jnp.where(mP, iota_k, 0), axis=0)
    lor = jnp.where((inP == 1) & (glP == 0), nl0_ref[1] + slotP, lor)

    mA = lor[None, :] == tbl_ref[:, 0:1]                   # [128, R]
    glA = jnp.sum(jnp.where(mA, dec & 1, 0), axis=0)       # [R]
    inA = jnp.sum(jnp.where(mA, 1, 0), axis=0)
    slotA = jnp.sum(jnp.where(mA, iota_k, 0), axis=0)
    nl0 = nl0_ref[0]
    new_lor = jnp.where((inA == 1) & (glA == 0), nl0 + slotA, lor)
    newlor_ref[0, :] = new_lor

    mC = new_lor[None, :] == tbl_ref[:K, 2:3]              # [K, R]
    oh_small = mC & (((dec[:K, :] >> 1) & 1) == 1)

    W = _make_W(v_ref[...], oh_small, C, K, quantized)
    xx_all = x_ref[...].astype(jnp.int32)                  # [T, R]
    if HB > 1:
        xx_all = xx_all & 0xFF
    _hist_chunks(xx_all, W, out_ref, Fc, C=C, K=K, LO=LO, HB=HB,
                 quantized=quantized)

    @pl.when(n == n_blocks - 1)
    def _():
        _fused_scan_tiled(out_ref, parent_ref, scal_ref, meta_ref,
                          fm_ref, rec_ref, ft * T, K=K, C=C, LO=LO,
                          HB=HB, T=T, Th=Th, B=B, KMAX=KMAX, RECW=RECW,
                          hp=hp, quantized=quantized)


def merge_tile_records(rec_tiles: jnp.ndarray, f_pad: int,
                       num_bins: int) -> jnp.ndarray:
    """[FT, REC_ROWS, RECW] per-tile scan records -> [REC_ROWS, RECW]:
    per record column, pick the tile whose best cell the UNTILED flat
    argmax would have picked. jnp.argmax order is NaN-maximal, then
    value, then lowest flat (d, f, b) index; the tiled path's filtered
    gain map is NaN-free (the `gain > min_gain_shift` filter maps NaN
    cells to -inf before the argmax), but NaN still ranks above +inf
    here for safety. Exact in f32: d/f/b are small exact integers and
    the flat key stays far below 2^24."""
    raw = rec_tiles[:, 12, :]                               # [FT, RECW]
    nan = jnp.isnan(raw)
    fin = jnp.where(nan, jnp.inf, raw)
    key = (rec_tiles[:, 3, :] * jnp.float32(f_pad * num_bins)
           + rec_tiles[:, 1, :] * jnp.float32(num_bins)
           + rec_tiles[:, 2, :])                            # [FT, RECW]
    best = rec_tiles[0]
    b_nan, b_fin, b_key = nan[0], fin[0], key[0]
    for t in range(1, rec_tiles.shape[0]):
        gt = fin[t] > b_fin
        eq = fin[t] == b_fin
        better = ((nan[t] & ~b_nan)
                  | ((nan[t] == b_nan) & (gt | (eq & (key[t] < b_key)))))
        best = jnp.where(better[None, :], rec_tiles[t], best)
        b_nan = jnp.where(better, nan[t], b_nan)
        b_fin = jnp.where(better, fin[t], b_fin)
        b_key = jnp.where(better, key[t], b_key)
    return best


@functools.partial(jax.jit,
                   static_argnames=("num_features", "num_slots",
                                    "num_bins", "kmax", "hp", "tile",
                                    "interpret", "wide_lo"))
def wave_pass_fused_tiled_pallas(
    X_binned_t: jnp.ndarray,   # [F(+pad), N] int8/uint8 (feature-major)
    vals: jnp.ndarray,         # [C, N] f32 (bag-masked) or int8 (quantized)
    dec: jnp.ndarray,          # [128, N] i8 decision bits (wave_apply
    #   layout + bit2 = pending-wave apply go-left)
    leaf_of_row: jnp.ndarray,  # [N] int32
    table: jnp.ndarray,        # [T_ROWS, 128] int32 semantic wave table
    pend_leaf: jnp.ndarray,    # [128] i32 deferred-relabel applied leaf
    #   ids (-1 = inactive; all -1 disables the pending pass)
    pend_nl0: jnp.ndarray,     # [] i32 pending wave's first new leaf id
    parent_hist: jnp.ndarray,  # [kmax, C*F*B] f32/i32 candidate parent hists
    scal: jnp.ndarray,         # [8, 2*kmax] f32 (pack_fused_scalars)
    meta_tiles: jnp.ndarray,   # [8, FT*128] i32 (pack_fused_meta_tiled)
    fmask_tiles: jnp.ndarray,  # [fmask_rows, FT*128] i32 per-child masks
    num_features: int,         # true F (pre-padding)
    num_slots: int,
    num_bins: int,
    kmax: int,
    hp: SplitHyperParams,
    tile: int = 32,
    interpret: bool = False,
    wide_lo: int = 128,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Feature-tiled fused wave: returns (new_leaf_of_row [N] i32,
    hist [K, C, F, num_bins], rec [REC_ROWS, RECW] f32 — already
    cross-tile merged; row 12 keeps the winner's raw argmax gain).

    X/vals/dec may be pre-padded (features to FT*tile, rows to a block
    multiple) by the caller so the pad cost is paid once per tree;
    `leaf_of_row` keeps the true row count."""
    F = num_features
    C = vals.shape[0]
    N = leaf_of_row.shape[0]
    K = num_slots
    quantized = vals.dtype == jnp.int8
    B_lane, LO, HB = _compute_dims(num_bins, wide_lo)
    FT = -(-F // tile)
    Fpad = FT * tile
    rows_t = HB * C * K
    Fc = _feat_chunk(tile, LO, rows_t)
    Th = _round_up(tile, Fc)
    RECW = rec_width(kmax)
    NX = X_binned_t.shape[1]
    n_blk = N_BLK if NX >= N_BLK else max(_round_up(NX, 256), 256)
    Np = _round_up(NX, n_blk)

    X = X_binned_t.astype(jnp.int8)
    if X.shape != (Fpad, Np):
        X = jnp.pad(X, ((0, Fpad - X.shape[0]), (0, Np - X.shape[1])))
    v = vals if quantized else vals.astype(jnp.float32)
    if v.shape[1] != Np:
        v = jnp.pad(v, ((0, 0), (0, Np - v.shape[1])))
    d8 = dec.astype(jnp.int8)
    if d8.shape[1] != Np:
        d8 = jnp.pad(d8, ((0, 0), (0, Np - d8.shape[1])))
    lor = leaf_of_row.astype(jnp.int32)
    if Np != N:
        lor = jnp.pad(lor, (0, Np - N), constant_values=-1)
    t = table.astype(jnp.int32)
    zero = t[_T_NL0] * 0
    tblp = jnp.stack([t[0], pend_leaf.astype(jnp.int32), t[7], zero,
                      zero, zero, zero, zero], axis=1)      # [128, 8]
    nl0 = jnp.stack([t[_T_NL0, 0],
                     jnp.asarray(pend_nl0, jnp.int32)])     # [2]

    acc = jnp.int32 if quantized else jnp.float32
    CFB = C * F * num_bins
    assert parent_hist.shape[1] == CFB, (parent_hist.shape, (K, CFB))
    # relay the parent histograms tile-major: block ft holds its own
    # tile's [K, C*tile*B] slab (padded features carry zeros; their
    # num_bins=0 meta already maps them to gain -inf)
    par = parent_hist.astype(acc)[:K].reshape(K, C, F, num_bins)
    par = jnp.pad(par, ((0, 0), (0, 0), (0, Fpad - F), (0, 0)))
    par = par.reshape(K, C, FT, tile, num_bins) \
        .transpose(2, 0, 1, 3, 4).reshape(FT * K, C * tile * num_bins)

    KP = fmask_rows(kmax)
    assert meta_tiles.shape == (8, FT * 128), meta_tiles.shape
    assert fmask_tiles.shape == (KP, FT * 128), fmask_tiles.shape

    n_blocks = Np // n_blk
    kernel = functools.partial(_fused_tiled_kernel, K=K, C=C, LO=LO,
                               HB=HB, T=tile, Fc=Fc, Th=Th, B=num_bins,
                               KMAX=kmax, RECW=RECW, hp=hp,
                               quantized=quantized, n_blocks=n_blocks)
    newlor, out, rec = pl.pallas_call(
        kernel,
        grid=(FT, n_blocks),
        in_specs=[
            pl.BlockSpec((tile, n_blk), lambda ft, n: (ft, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, n_blk), lambda ft, n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((128, n_blk), lambda ft, n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_blk), lambda ft, n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((128, 8), lambda ft, n: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((K, C * tile * num_bins), lambda ft, n: (ft, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((8, 128), lambda ft, n: (0, ft),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((KP, 128), lambda ft, n: (0, ft),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, n_blk), lambda ft, n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows_t, Th * LO), lambda ft, n: (ft, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((REC_ROWS, RECW), lambda ft, n: (ft, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Np), jnp.int32),
            jax.ShapeDtypeStruct((FT * rows_t, Th * LO), acc),
            jax.ShapeDtypeStruct((FT * REC_ROWS, RECW), jnp.float32),
        ],
        name=kernel_name("fused_tiled", k=K, t=tile, b=num_bins,
                         q=quantized),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * K * C * FT * Th * Np * B_lane
            + FT * 2 * K * 3 * tile * B_lane * 8,
            bytes_accessed=FT * (tile + 128) * Np + (C * 4 + 8) * Np
            + FT * rows_t * Th * LO * 4 + FT * K * C * tile * num_bins * 4,
            transcendentals=0,
        ),
    )(X, v, d8, lor[None, :], tblp, par, meta_tiles, fmask_tiles, scal,
      nl0)

    hist_t = out.reshape(FT, rows_t, Th * LO)
    hist = jax.vmap(
        lambda o: _unflatten_hist(o, K, C, tile, Th, LO, HB, num_bins)
    )(hist_t)                                   # [FT, K, C, tile, B]
    hist = hist.transpose(1, 2, 0, 3, 4) \
        .reshape(K, C, Fpad, num_bins)[:, :, :F, :]
    rec_m = merge_tile_records(rec.reshape(FT, REC_ROWS, RECW),
                               Fpad, num_bins)
    return newlor[0, :N], hist, rec_m


def unpack_fused_records(rec: jnp.ndarray, kmax: int):
    """[REC_ROWS, RECW] record block -> SplitResult of [2*kmax] arrays
    (left children at [0, kmax), right at [kmax, 2*kmax)) in exact field
    order. Integer fields are exact small integers in f32."""
    from .split import SplitResult
    r = rec[:, :2 * kmax]
    return SplitResult(
        gain=r[0],
        feature=r[1].astype(jnp.int32),
        threshold=r[2].astype(jnp.int32),
        default_left=r[3] > 0.5,
        left_sum_g=r[4], left_sum_h=r[5], left_count=r[6],
        right_sum_g=r[7], right_sum_h=r[8], right_count=r[9],
        left_output=r[10], right_output=r[11],
    )
