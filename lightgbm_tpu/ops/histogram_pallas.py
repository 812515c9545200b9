"""Fused Pallas TPU histogram kernels — hot loop #1 of the framework.

TPU-native re-design of the CUDA shared-memory histogram kernel
(CUDAConstructHistogramDenseKernel, cuda_histogram_constructor.cu:20-72):
there, each thread block accumulates a per-block histogram in shared memory
with atomicAdd and flushes to global memory. TPUs have no atomics; the
equivalent play is:

  * VMEM is the "shared memory": the output block stays resident in VMEM
    while the grid walks row-chunks (the revisit-accumulate pattern replaces
    the atomic flush),
  * the scatter-add over bins becomes an on-the-fly one-hot (iota compare in
    VMEM, never materialized to HBM) contracted against the value channels on
    the MXU: hist[c, b] += vals[c, r] * (bins[r] == b).

Contraction layout (the round-3 redesign; the first version ran one skinny
matmul per feature pair and re-laid the result into a [K, C, F, B] block,
which measured ~13% MXU utilization): per row-block the kernel

  1. builds the slot mask ONE broadcast compare [K, R] and the weight
     matrix W = vals (x) slot_onehot as a single [C*K, R] array,
  2. builds a CONCATENATED one-hot for a chunk of features in VMEM scratch:
     oh[f*LO + b, r] = (bin[f, r] == b), shape [Fc*LO, R],
  3. runs ONE large matmul W @ oh^T -> [C*K, Fc*LO] per chunk and adds it
     into the flat output block out[C*K, F*LO] — a perfectly lane-tiled
     accumulate (no per-feature strided writes).

The [K, C, F, B] shape is restored OUTSIDE the kernel by one tiny reshape/
transpose. Bins wider than 128 (B = 256) run HB = 2 passes with the high
bin bit folded into the one-hot build; the output rows become [HB*C*K].

Kernels:

  build_histogram_slots_pallas  K histogram sets in one pass -> [K, C, F, B]
  build_histogram_pallas        single set (K = 1 wrapper)    -> [C, F, B]
  wave_pass_pallas              fused split-apply (row relabel) + candidate
                                smaller-child membership + slot histogram
                                over those children's rows alone, compacted
                                in VMEM first
  take_leaf_values_pallas       exact values[leaf_of_row] gather

The MXU contraction runs in bfloat16 with float32 accumulation: one-hot
entries are exact in bf16, gradient/hessian values round to 8 mantissa bits
before the exact f32 accumulation (the same single-precision-histogram
trade the reference's GPU learner makes, docs/GPU-Performance.rst; the
count channel stays exact since its values are 0/1). int8 `vals` run the
contraction as s8 x s8 -> s32 (the analog of the reference's discretized
histogram kernels, cuda_histogram_constructor.cu:253-527) — exact integer
accumulation.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import kernel_name, round_up as _round_up

F_BLK = 32          # int8 sublane tile
N_BLK = 2048        # rows per grid step

# Mosaic's default scoped-VMEM cap (16 MiB on a v5e, of 128 MiB physical)
# is below the wide-bin (B = 256) float contraction's working set: the
# hi/lo build keeps the [Fc*LO, R] bf16 lo one-hot AND one hi-masked copy
# live (2 x 8 MB by the _feat_chunk budget) beside the output block. The
# histogram kernels therefore state their own cap.
HIST_VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def _compute_dims(num_bins: int, wide_lo: int = 128):
    """B padded to a lane-friendly width; LO = one-hot compare width,
    HB = number of LO-wide sub-blocks of the bin axis.

    `wide_lo` picks the hi/lo decomposition for bins wider than 128
    (docs/PERF.md): 128 = the legacy two-pass split, 64 = the hi/lo
    variant (2-bit hi part, 64-wide lo one-hot built once and masked per
    hi value — 4 narrow matmuls instead of one 256-wide one-hot). Bin
    codes decompose as bin = hi * LO + lo either way, so the two
    variants produce bit-identical histograms."""
    if num_bins <= 32:
        B = 32
    elif num_bins <= 64:
        B = 64
    elif num_bins <= 128:
        B = 128
    else:
        B = 256
    LO = min(B, 128)
    if B > 128 and wide_lo in (32, 64):
        LO = wide_lo
    HB = B // LO
    return B, LO, HB


def _feat_chunk(F: int, LO: int, rows: int) -> int:
    """Features per one-hot chunk. Every chunk costs one matmul whose
    latency dominates at small K (measured ~2 us/block on v5e), so the
    chunk count is the MINIMUM satisfying the VMEM budgets: the
    [Fc*LO, R] bf16 one-hot value stays <= 8 MB (<= 2048 lanes at
    R=2048) and the [rows, Fc*LO] f32 output block <= ~3.4 MB. Chunks
    are balanced (28 features -> 1x28 when it fits, else 2x14 — never
    16+12pad: padded features cost real MXU MACs) and 128-lane aligned."""
    align = max(128 // LO, 1)
    n_chunks = 1
    while True:
        fc = _round_up(-(-F // n_chunks), align)
        if (fc * LO <= 2048 and rows * fc * LO * 4 <= 3_400_000) \
                or fc <= align:
            return fc
        n_chunks += 1


def _accum_chunk(xx, W, out_ref, col0, *, C, K, LO, HB, quantized):
    """Accumulate one feature-chunk's histogram: xx [Fc, R] i32 bins,
    W [C*K, R]; adds into out_ref[hb*C*K:(hb+1)*C*K, col0 : col0+Fc*LO].

    The concatenated one-hot is fed to the matmul as a VALUE (not via a
    VMEM scratch ref): letting Mosaic schedule its materialization saves
    the explicit scratch round-trip (~2.6 ms per full-data pass measured
    on v5e)."""
    Fc, R = xx.shape
    w_dtype = jnp.int8 if quantized else jnp.bfloat16
    acc = jnp.int32 if quantized else jnp.float32
    iota3 = jax.lax.broadcasted_iota(jnp.int32, (Fc, LO, R), 1)
    if HB == 1:
        oh = (xx[:, None, :] == iota3).reshape(Fc * LO, R).astype(w_dtype)
        part = jax.lax.dot_general(
            W, oh, (((1,), (1,)), ((), ())),
            preferred_element_type=acc)                 # [C*K, Fc*LO]
        out_ref[:, col0:col0 + Fc * LO] += part
    else:
        lo = xx & (LO - 1)
        hi = xx >> (LO.bit_length() - 1)
        if quantized:
            # v5e Mosaic has no int8 vector select — build each pass's
            # one-hot directly from the bool conjunction and narrow once
            for hb in range(HB):
                oh = ((lo[:, None, :] == iota3)
                      & (hi == hb)[:, None, :]).reshape(Fc * LO, R) \
                    .astype(w_dtype)
                part = jax.lax.dot_general(
                    W, oh, (((1,), (1,)), ((), ())),
                    preferred_element_type=acc)
                out_ref[hb * C * K:(hb + 1) * C * K,
                        col0:col0 + Fc * LO] += part
        else:
            # hi/lo split: the LO-wide one-hot is compared AND converted
            # ONCE; each hi pass only masks it with a 0/1 bf16 broadcast
            # multiply. At LO=64/HB=4 that cuts the per-(feature, row)
            # VPU volume roughly in half vs compare+convert per pass —
            # the 255-bin one-hot build is VPU-bound, the MXU MAC count
            # (HB*LO = B) is identical for every decomposition. The mask
            # is exactly 0.0/1.0 so every product (and therefore the f32
            # accumulation) is bit-identical to the fused compare.
            oh_lo = (lo[:, None, :] == iota3).astype(w_dtype)  # [Fc,LO,R]
            for hb in range(HB):
                oh = (oh_lo * (hi == hb)[:, None, :].astype(w_dtype)) \
                    .reshape(Fc * LO, R)
                part = jax.lax.dot_general(
                    W, oh, (((1,), (1,)), ((), ())),
                    preferred_element_type=acc)
                out_ref[hb * C * K:(hb + 1) * C * K,
                        col0:col0 + Fc * LO] += part


def _make_W(v, oh_slot, C, K, quantized):
    """[C*K, R] channel-major weights: W[c*K + k, r] = vals[c, r] when
    slot r == k else 0. One broadcast multiply/select — no per-slot loop."""
    R = v.shape[1]
    if quantized:
        # v5e Mosaic has no int8 vector select — mask in i32, then narrow
        W = jnp.where(oh_slot[None, :, :],
                      v.astype(jnp.int32)[:, None, :], 0).astype(jnp.int8)
    else:
        W = oh_slot[None, :, :].astype(jnp.bfloat16) \
            * v.astype(jnp.bfloat16)[:, None, :]
    return W.reshape(C * K, R)


def _hist_chunks(xx_all, W, out_ref, Fc, *, C, K, LO, HB,
                 quantized):
    """Walk the block's features in exact chunks of Fc, accumulating into
    out_ref. Chunks past the real feature count are padded with bin -1
    (never one-hot-matched), so padded output columns stay zero."""
    Fb = xx_all.shape[0]
    Fh = out_ref.shape[1] // LO
    for f0 in range(0, Fh, Fc):
        xx = xx_all[f0:f0 + min(Fc, max(Fb - f0, 0)), :]
        if xx.shape[0] < Fc:
            xx = jnp.pad(xx, ((0, Fc - xx.shape[0]), (0, 0)),
                         constant_values=-1)
        _accum_chunk(xx, W, out_ref, f0 * LO, C=C, K=K, LO=LO,
                     HB=HB, quantized=quantized)


def _slots_kernel(x_ref, v_ref, s_ref, out_ref, *, K, C, LO, HB,
                  Fc, quantized):
    """Grid (F_blocks, N_blocks); N varies fastest so out_ref stays
    resident across the row sweep of each feature block.

    x_ref  [Fb, R]  int8        binned features (this block)
    v_ref  [C, R]   f32 / int8  value channels (bag-masked)
    s_ref  [1, R]   int32       slot id per row; outside [0, K) = none
    out_ref[HB*C*K, Fh*LO]      f32 / int32 (flat histogram block)
    """
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    R = v_ref.shape[1]
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (K, R), 0)
    oh_slot = s_ref[0:1, :] == iota_k                   # [K, R]
    W = _make_W(v_ref[...], oh_slot, C, K, quantized)
    xx_all = x_ref[...].astype(jnp.int32)
    if HB > 1:
        xx_all = xx_all & 0xFF
    _hist_chunks(xx_all, W, out_ref, Fc, C=C, K=K, LO=LO, HB=HB,
                 quantized=quantized)


def _unflatten_hist(out, K, C, F, Fp, LO, HB, num_bins):
    """[HB*C*K, Fp*LO] -> [K, C, F, num_bins]."""
    h = out.reshape(HB, C, K, Fp, LO).transpose(2, 1, 3, 0, 4)
    return h.reshape(K, C, Fp, HB * LO)[:, :, :F, :num_bins]


@functools.partial(jax.jit,
                   static_argnames=("num_slots", "num_bins", "interpret",
                                    "wide_lo"))
def build_histogram_slots_pallas(
    X_binned_t: jnp.ndarray,   # [F, N] int8/uint8 (feature-major)
    vals: jnp.ndarray,         # [C, N] f32 (bag-masked) or int8 (quantized)
    slot: jnp.ndarray,         # [N] int32
    num_slots: int,
    num_bins: int,
    interpret: bool = False,
    wide_lo: int = 128,
) -> jnp.ndarray:
    """Wave histogram on TPU: returns [K, C, F, num_bins] float32, or
    int32 when `vals` is int8 (quantized-gradient training). `wide_lo`
    selects the wide-bin (>128) hi/lo decomposition (_compute_dims)."""
    F, N = X_binned_t.shape
    C = vals.shape[0]
    K = num_slots
    quantized = vals.dtype == jnp.int8
    B, LO, HB = _compute_dims(num_bins, wide_lo)
    rows = HB * C * K
    Fc_n = _feat_chunk(F, LO, rows)
    if F <= 32 and rows * _round_up(F, Fc_n) * LO * 4 <= 3_400_000:
        # narrow: one feature block holding ALL features (block == array
        # dim satisfies the sublane-tiling rule without padding F), exact
        # internal chunks — 28 features cost 28 features' MACs. Requires
        # the whole [rows, F*LO] output block to fit the VMEM budget;
        # wide waves at wide bins (e.g. K=128, C=3, B=256) fall through
        # to the gridded path below.
        Fc = Fc_n
        Fb, Fp = F, F
        Fh = _round_up(F, Fc)
    else:
        # wide: grid over 8-aligned feature blocks (block histograms
        # stream through VMEM one block at a time)
        Fc = max(_feat_chunk(F, LO, rows) // 8 * 8, 8)
        Fb, Fh = Fc, Fc
        Fp = _round_up(F, Fc)
    n_blk = N_BLK if N >= N_BLK else max(_round_up(N, 256), 256)
    Np = _round_up(N, n_blk)

    X = X_binned_t.astype(jnp.int8)
    if Fp != F or Np != N:
        X = jnp.pad(X, ((0, Fp - F), (0, Np - N)))
    v = vals if quantized else vals.astype(jnp.float32)
    s = slot.astype(jnp.int32)
    if Np != N:
        v = jnp.pad(v, ((0, 0), (0, Np - N)))
        s = jnp.pad(s, (0, Np - N), constant_values=-1)

    out_dtype = jnp.int32 if quantized else jnp.float32
    n_fblocks = Fp // Fb
    out_cols = n_fblocks * Fh * LO
    grid = (n_fblocks, Np // n_blk)
    kernel = functools.partial(_slots_kernel, K=K, C=C, LO=LO, HB=HB,
                               Fc=Fc, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((Fb, n_blk), lambda f, n: (f, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, n_blk), lambda f, n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_blk), lambda f, n: (0, n),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rows, Fh * LO), lambda f, n: (0, f),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, out_cols), out_dtype),
        name=kernel_name("hist_slots", k=K, b=num_bins, lo=LO,
                         q=quantized),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=HIST_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * K * C * (out_cols // LO) * Np * B,
            bytes_accessed=Fp * Np + (C * 4 + 4) * Np + rows * out_cols * 4,
            transcendentals=0,
        ),
    )(X, v, s[None, :])

    return _unflatten_hist(out, K, C, F, out_cols // LO, LO, HB, num_bins)


def _leaf_values_kernel(lor_ref, val_ref, out_ref, *, Lp):
    """out[r] = val[lor[r]] as an exact one-hot contraction (XLA's native
    [N]-gather from a tiny table runs at ~0.6 GB/s on this target; the
    one-hot matmul streams at HBM speed). Out-of-range lor rows yield 0."""
    lor = lor_ref[0, :]                                    # [R] i32
    iota = jax.lax.broadcasted_iota(jnp.int32, (Lp, lor.shape[0]), 0)
    oh = (lor[None, :] == iota).astype(jnp.float32)        # [Lp, R]
    # HIGHEST: exactly one 1.0 x value product per row -> exact f32
    out_ref[...] = jax.lax.dot_general(
        val_ref[...], oh, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                # [1, R]


@functools.partial(jax.jit, static_argnames=("interpret",))
def take_leaf_values_pallas(
    values: jnp.ndarray,       # [L] f32 per-leaf values
    leaf_of_row: jnp.ndarray,  # [N] int32
    interpret: bool = False,
) -> jnp.ndarray:
    """Exact values[leaf_of_row] -> [N] f32 on TPU."""
    L, = values.shape
    N, = leaf_of_row.shape
    Lp = _round_up(L, 8)
    n_blk = 4096 if N >= 4096 else max(_round_up(N, 256), 256)
    # bound the [Lp, n_blk] f32 one-hot to ~4 MB of VMEM
    while Lp * n_blk * 4 > 4_194_304 and n_blk > 256:
        n_blk //= 2
    Np = _round_up(N, n_blk)
    v = values.astype(jnp.float32)
    if Lp != L:
        v = jnp.pad(v, (0, Lp - L))
    lor = leaf_of_row.astype(jnp.int32)
    if Np != N:
        lor = jnp.pad(lor, (0, Np - N), constant_values=-1)
    out = pl.pallas_call(
        functools.partial(_leaf_values_kernel, Lp=Lp),
        grid=(Np // n_blk,),
        in_specs=[
            pl.BlockSpec((1, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Lp), lambda n: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, n_blk), lambda n: (0, n),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.float32),
        name=kernel_name("take_leaf_values", l=Lp),
        interpret=interpret,
    )(lor[None, :], v[None, :])
    return out[0, :N]


# ---------------------------------------------------------------------------
# Wave megakernel: one fused pass per wave doing split APPLICATION (row
# relabel), candidate smaller-child membership, and the slot histogram.
# The unfused path materializes several [N]-sized intermediates between
# XLA ops (leaf relabel pass, candidate pass, slot ids) that each run at
# a few GB/s; fusing them into the histogram's row sweep makes the whole
# wave cost one X read plus the MXU contractions. Reference semantics:
# DataPartition::Split (data_partition.hpp:102) for the relabel and
# Dataset::ConstructHistograms (dataset.h:745) for the histogram — one
# kernel instead of the reference's three hot loops.
#
# The caller-facing wave table keeps the 16-row semantic layout below; the
# wrapper packs each entry's value fields into ONE int32 so the in-kernel
# per-row lookups are single masked reductions over a [K, R] leaf-match
# mask instead of 8-value select chains:
#   packed = feat | thr<<10 | default_left<<19 | miss_bin<<20
#            | smaller_is_left<<29 | active<<30
# where miss_bin pre-resolves the missing test (default_bin for
# MissingType::Zero, num_bins-1 for NaN, unreachable 0x1FF for None).
# ---------------------------------------------------------------------------

# rows of the semantic [T_ROWS, 128] i32 wave table
_T_APP_LEAF, _T_APP_FEAT, _T_APP_THR, _T_APP_DL, _T_APP_MT, _T_APP_DB, \
    _T_APP_NB, _T_CAND_LEAF, _T_CAND_FEAT, _T_CAND_THR, _T_CAND_DL, \
    _T_CAND_MT, _T_CAND_DB, _T_CAND_NB, _T_CAND_SIL, _T_NL0 = range(16)
T_ROWS = 16
_MT_ZERO = 1      # must match models/tree.py MISSING_ZERO
_MT_NAN = 2       # must match models/tree.py MISSING_NAN
_MISS_NONE = 0x1FF  # unreachable bin sentinel (cols are 8-bit)

# packed wave-table entry bit layout (storage F <= 32, bins <= 256):
#   feat 0:5 | thr 5:13 | dl 13:14 | miss_bin 14:23 | sil 23:24
#   | valid 24:25 | slot 25:32


def _pack_wave_table(table: jnp.ndarray) -> jnp.ndarray:
    """[T_ROWS, 128] semantic table -> [128, 8] i32 packed/transposed:
    col 0 applied leaf id (-1 inactive), col 1 applied packed fields,
    col 2 candidate leaf id, col 3 candidate packed fields."""
    t = table.astype(jnp.int32)

    def miss_bin(mt, db, nb):
        return jnp.where(mt == _MT_ZERO, db,
                         jnp.where(mt == _MT_NAN, nb - 1, _MISS_NONE))

    slot = jnp.arange(128, dtype=jnp.int32)

    def pack(leaf, feat, thr, dl, mb, sil):
        p = ((feat & 31) | (thr << 5) | (dl << 13) | (mb << 14)
             | (sil << 23) | (1 << 24) | (slot << 25))
        return jnp.where(leaf >= 0, p, 0)

    zero = jnp.zeros((128,), jnp.int32)
    p_app = pack(t[_T_APP_LEAF], t[_T_APP_FEAT], t[_T_APP_THR],
                 t[_T_APP_DL],
                 miss_bin(t[_T_APP_MT], t[_T_APP_DB], t[_T_APP_NB]), zero)
    p_cand = pack(t[_T_CAND_LEAF], t[_T_CAND_FEAT], t[_T_CAND_THR],
                  t[_T_CAND_DL],
                  miss_bin(t[_T_CAND_MT], t[_T_CAND_DB], t[_T_CAND_NB]),
                  t[_T_CAND_SIL])
    cols = [t[_T_APP_LEAF], p_app, t[_T_CAND_LEAF], p_cand,
            zero, zero, zero, zero]
    return jnp.stack(cols, axis=1)                        # [128, 8]


def _masked_pick(m, col):
    """Per-row table value: sum_k m[k, r] * col[k] — rows match at most
    one table entry, so the masked sum IS the select."""
    return jnp.sum(jnp.where(m, col, 0), axis=0)          # [R] i32


# ---- categorical tables: every entry's bitset beside the packed table.
# A categorical split sends a row left when bit `bin` of its left-set is
# set (ops/categorical.py; bin 0, the other/NaN bin, is never in it). On
# a table that holds one, the kernels decide EVERY entry by a bitset over
# the bin domain: a numeric entry's is the prefix {b <= thr}, so
# `col == miss_bin ? default_left : bit(col)` is its own rule unchanged,
# and a categorical entry's missing rule is turned off (its bin 0 is in
# the bitset's zeros). The bitsets are a second operand, present only on
# such a table (a numeric table's kernels take the operands and the body
# they had before): the kernels read them as bytes, and a row picks its
# entry's bytes with one small matmul against its entry mask (the MXU,
# not W masked sums on the VPU), then byte `bin >> 3`, bit `bin & 7`.

def wave_cat_words(table, app_is_cat, app_bits, cand_is_cat, cand_bits):
    """(table', words): the semantic table with the categorical entries'
    missing type set to none, and [128, 2W] i32 bitset words for the
    applied entries (app_*: [K] bool, [K, W] u32) and the candidates."""
    t = table.astype(jnp.int32)
    W = app_bits.shape[1]

    def words(is_cat, bits, thr):
        K = is_cat.shape[0]
        n = jnp.clip(thr[:K, None] + 1 - 32 * jnp.arange(W)[None, :],
                     0, 32).astype(jnp.uint32)             # [K, W]
        prefix = jnp.where(n >= 32, jnp.uint32(0xFFFFFFFF),
                           (jnp.uint32(1) << jnp.minimum(n, 31))
                           - jnp.uint32(1))
        w = jnp.where(is_cat[:, None], bits.astype(jnp.uint32), prefix)
        w = jax.lax.bitcast_convert_type(w, jnp.int32)
        return jnp.pad(w, ((0, 128 - K), (0, 0)))

    def no_miss(mt, is_cat):
        K = is_cat.shape[0]
        return mt.at[:K].set(jnp.where(is_cat, 0, mt[:K]))

    t = t.at[_T_APP_MT].set(no_miss(t[_T_APP_MT], app_is_cat)) \
        .at[_T_CAND_MT].set(no_miss(t[_T_CAND_MT], cand_is_cat))
    cw = jnp.concatenate([words(app_is_cat, app_bits, t[_T_APP_THR]),
                          words(cand_is_cat, cand_bits, t[_T_CAND_THR])],
                         axis=1)
    return t, cw


def _cat_operand(cat):
    """(kernel kwargs, operands, in_specs) for an optional [128, 2W]
    bitset operand: nothing at all on a numeric table. The kernels take
    it as bytes, [8W, 128] float32 (0..255): row ``4w + j`` is byte j of
    word w of each entry's applied bitset (bins ``32w + 8j`` up), rows
    from 4W on the candidate's."""
    if cat is None:
        return {}, [], []
    W = cat.shape[1] // 2
    byts = (cat.astype(jnp.int32)[:, :, None]
            >> (8 * jnp.arange(4, dtype=jnp.int32))) & 0xFF   # [128, 2W, 4]
    byts = byts.reshape(128, 8 * W).T.astype(jnp.float32)
    return ({"W": W}, [byts],
            [pl.BlockSpec((8 * W, 128), lambda n: (0, 0),
                          memory_space=pltpu.VMEM)])


def _pick_bytes(m, cat_ref, first, W):
    """The bitset bytes of each row's own table entry, [4W, R]: one small
    matmul of the byte table (rows ``first..first+4W`` of cat_ref, [4W,
    128] f32 holding 0..255, entry-major along the lanes) with the rows'
    [128, R] entry mask. Each output sums one byte x 1 product, so
    bfloat16 operands and a float32 result are exact."""
    tb = cat_ref[first:first + 4 * W, :].astype(jnp.bfloat16)
    return jax.lax.dot_general(
        tb, m.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)


def _bit_of(byts, col):
    """Bit ``col`` of a row's bin-domain bitset ([4W, R] bytes: byte
    ``col >> 3``, bit ``col & 7``)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, byts.shape, 0)
    byte = jnp.sum(jnp.where(iota == (col >> 3)[None, :], byts, 0), axis=0)
    return (byte >> (col & 7)) & 1


def _wave_logic(x_ref, v_ref, lor_ref, tbl_ref, nl0_ref, newlor_ref, *,
                K, C, F, HB, quantized, with_hist, cat_ref=None, W=0):
    """Shared relabel + candidate-membership body. The APPLY side always
    walks all 128 table rows (inactive rows have leaf -1 and never match
    — [128, R] compares cost ~2 VPU ops/row-block, so there is nothing
    to bucket), while the candidate side is bucketed to K because the
    MXU contraction cost scales with it. Returns (bins [F, R] i32,
    candidate entry mask [K, R], in a candidate's smaller child [R],
    that candidate's slot [R]); None when with_hist=False. With
    ``cat_ref`` (a categorical table, W > 0) every entry decides by its
    bitset (wave_cat_words)."""
    R = lor_ref.shape[1]
    xx_log = x_ref[0:F, :].astype(jnp.int32)               # [F, R]
    if HB > 1:
        xx_log = xx_log & 0xFF
    iota_f = jax.lax.broadcasted_iota(jnp.int32, (F, R), 0)

    def go_left(p, byts=None):
        feat = p & 31
        thr = (p >> 5) & 0xFF
        dl = (p >> 13) & 1
        mb = (p >> 14) & 0x1FF
        col = jnp.sum(jnp.where(feat[None, :] == iota_f, xx_log, 0),
                      axis=0)                              # [R]
        if byts is not None:
            return jnp.where(col == mb, dl, _bit_of(byts, col))
        return jnp.where(col == mb, dl, (col <= thr).astype(jnp.int32))

    # ---- applied splits: relabel rows of split leaves
    lor = lor_ref[0, :]                                    # [R] i32
    mA = lor[None, :] == tbl_ref[:, 0:1]                   # [128, R]
    pA = _masked_pick(mA, tbl_ref[:, 1:2])
    glA = go_left(pA, _pick_bytes(mA, cat_ref, 0, W) if W else None)
    nl0 = nl0_ref[0]
    new_lor = jnp.where((((pA >> 24) & 1) == 1) & (glA == 0),
                        nl0 + ((pA >> 25) & 127), lor)
    newlor_ref[0, :] = new_lor
    if not with_hist:
        return None

    # ---- candidate membership on the post-apply leaf
    mC = new_lor[None, :] == tbl_ref[:K, 2:3]              # [K, R]
    pC = _masked_pick(mC, tbl_ref[:K, 3:4])
    # the bytes are picked over all 128 entries (those past K are
    # inactive and match no row), so the matmul's contraction is whole
    glC = go_left(pC, _pick_bytes(new_lor[None, :] == tbl_ref[:, 2:3],
                                  cat_ref, 4 * W, W) if W else None)
    silC = (pC >> 23) & 1
    in_small = (((pC >> 24) & 1) == 1) & (glC == silC)     # [R]
    return xx_log, mC, in_small, (pC >> 25) & 127


def _wave_relabel_kernel(x_ref, v_ref, lor_ref, tbl_ref, nl0_ref, *refs,
                         C, F, HB, quantized, W=0):
    """Relabel-only wave (a tree's final wave has applied splits but no
    candidates left — paying a full histogram pass there is pure waste)."""
    cat_ref, (newlor_ref,) = (refs[0], refs[1:]) if W else (None, refs)
    _wave_logic(x_ref, v_ref, lor_ref, tbl_ref, nl0_ref, newlor_ref,
                K=0, C=C, F=F, HB=HB, quantized=quantized, with_hist=False,
                cat_ref=cat_ref, W=W)


# ---- the wave pass contracts only the rows that carry weight. The
# rows of a block that lie in a candidate's smaller child (about a fifth
# of them over a tree, never more than half in one wave) are moved, in
# order, into a VMEM staging buffer; the others never reach a one-hot.
# Each 128-row sub-tile is placed by ONE small matmul: its data (bins,
# value channels, slot + 1) against a [2*128, 128] one-hot whose rows are
# the buffer's positions from the fill's 128-aligned floor on, added
# into that window, so every store stays lane-aligned. The placement is
# exact: every output sums one bfloat16 x 1 product, bins (<= 255) and
# slots (< 128) are integers bfloat16 holds, and the values are the ones
# _make_W rounds to bfloat16 (or int8) anyway. Whenever the buffer holds
# a full tile of T rows the tile runs the slot histogram's contraction;
# the grid's last step (on a categorical table the last of each row
# partition, so a tile's rows belong to one partial histogram:
# wave_parts) contracts the rest, its tail zeros: slot 0 is no slot, so
# those lanes weigh nothing. On the chip (docs/PERF.md section 7d) a
# branch around an empty sub-tile made the placement about ten times
# slower, so every sub-tile is placed; a loop step places eight of
# them: one a step left each matmul's latency bare (a tree 40 % slower),
# all sixteen unrolled made the training program's first dispatch about
# 10 s slower.
_SUB = 128          # rows a placement sub-tile
_GROUP = 8          # sub-tiles a loop step places


def _stage_block(sel, dat_ref, stg_ref, fill_ref, idx_ref):
    """Append the block's rows whose ``sel`` [R] is set, in order, to the
    staging buffer stg_ref [D, 2T] at fill_ref[0]; dat_ref [D, R] f32
    holds the block's data, idx_ref [R / 128, 128] i32 takes each row's
    place among its sub-tile's selected rows. Positions past the fill
    hold zeros."""
    R = dat_ref.shape[1]
    nsub = R // _SUB
    sel = sel.astype(jnp.int32).reshape(nsub, _SUB)
    ii = jax.lax.broadcasted_iota(jnp.int32, (_SUB, _SUB), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (_SUB, _SUB), 1)
    # each row's selected predecessors in its sub-tile (counts < 128:
    # exact through the MXU); an unselected row's lies below any window
    pos = jax.lax.dot_general(
        sel.astype(jnp.bfloat16), (ii < jj).astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)
    idx_ref[...] = jnp.where(sel > 0, pos, -2 * _SUB)
    jt = jax.lax.broadcasted_iota(jnp.int32, (2 * _SUB, _SUB), 0)
    G = math.gcd(_GROUP, nsub)

    def place(g, fill):
        rows = idx_ref[pl.ds(pl.multiple_of(g * G, G), G), :]   # [G, S]
        cnts = [jnp.sum(jnp.where(rows[k:k + 1] >= 0, 1, 0))
                for k in range(G)]
        for k in range(G):
            off = fill & (_SUB - 1)
            base = pl.multiple_of(fill - off, _SUB)
            onehot = (jt == off + rows[k:k + 1]).astype(jnp.bfloat16)
            col = pl.multiple_of((g * G + k) * _SUB, _SUB)
            d = dat_ref[:, pl.ds(col, _SUB)].astype(jnp.bfloat16)
            stg_ref[:, pl.ds(base, 2 * _SUB)] += jax.lax.dot_general(
                d, onehot, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            fill = fill + cnts[k]
        return fill

    fill_ref[0] = jax.lax.fori_loop(0, nsub // G, place, fill_ref[0])


def _wave_kernel(x_ref, v_ref, lor_ref, tbl_ref, nl0_ref, *refs,
                 K, C, LO, HB, F, Fc, quantized, W=0):
    """Grid (N_blocks,). x_ref [F_pad, R]; v_ref [C, R]; lor_ref [1, R];
    tbl_ref [128, 8] i32 packed; nl0_ref [1] i32 in SMEM; on a
    categorical table (W > 0) also cat_ref [8W, 128] f32 bitset bytes and
    inb_ref [1, R] int8 in-bag rows; newlor_ref [1, R]; out_ref
    [HB*C*K, Fh*LO] (VMEM-resident across the whole grid); on a
    categorical table cnt_ref [K, 128] i32, each slot's in-bag rows
    (every lane the same; resident too); rows_ref [1] i32 in SMEM, the
    rows that reached the contraction. Scratch: dat_ref [D, R] f32 a
    block's staged data (rows 0:F bins, F:F+C values, F+C slot + 1; the
    rows past them are never read), stg_ref [D, 2R] f32 the staging
    buffer, fill_ref [1] i32 its fill, idx_ref [R / 128, 128] i32 the
    rows' places in their sub-tiles (_stage_block).

    All per-row logic runs either on full [F, R] / [K, R] tiles or on a
    handful of [1, R] ops — 1-sublane [1, R] chains are ~8x below VPU
    width, so the per-feature column extraction is a masked [F, R]
    reduction, and per-entry table values arrive as ONE packed int32 via
    a masked [K, R] reduction."""
    if W:
        (cat_ref, inb_ref, newlor_ref, out_ref, cnt_ref, rows_ref,
         dat_ref, stg_ref, fill_ref, idx_ref) = refs
    else:
        (newlor_ref, out_ref, rows_ref, dat_ref, stg_ref, fill_ref,
         idx_ref), cat_ref = refs, None
    # a categorical table's grid is (partition, block): each partition
    # starts its own histogram (wave_parts)
    ax = 1 if W else 0
    n = pl.program_id(ax)
    T = dat_ref.shape[1]

    @pl.when(n == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)
        stg_ref[...] = jnp.zeros_like(stg_ref)
        fill_ref[0] = 0
        if W:
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

    @pl.when((n == 0) & (pl.program_id(0) == 0))
    def _():
        rows_ref[0] = 0

    xx, mC, in_small, slot = _wave_logic(
        x_ref, v_ref, lor_ref, tbl_ref, nl0_ref, newlor_ref, K=K, C=C,
        F=F, HB=HB, quantized=quantized, with_hist=True, cat_ref=cat_ref,
        W=W)
    if W:
        # the smaller children's exact in-bag row counts
        inb = inb_ref[0, :].astype(jnp.int32) > 0
        cnt_ref[...] += jnp.sum(
            jnp.where(mC & (in_small & inb)[None, :], 1, 0),
            axis=1, keepdims=True)
    n_sel = jnp.sum(in_small.astype(jnp.int32))
    rows_ref[0] += n_sel

    @pl.when(n_sel > 0)
    def _():
        dat_ref[0:F, :] = xx.astype(jnp.float32)
        v = v_ref[...]
        dat_ref[F:F + C, :] = (v.astype(jnp.int32) if quantized
                               else v).astype(jnp.float32)
        dat_ref[F + C:F + C + 1, :] = (slot + 1).astype(jnp.float32)[None]
        _stage_block(in_small, dat_ref, stg_ref, fill_ref, idx_ref)

    def contract(i, carry):
        # ---- slot histogram of one staged tile (shared contraction)
        tile = stg_ref[:, 0:T]
        slot_t = tile[F + C:F + C + 1, :].astype(jnp.int32) - 1
        oh_slot = slot_t == jax.lax.broadcasted_iota(jnp.int32, (K, T), 0)
        Wv = _make_W(tile[F:F + C, :], oh_slot, C, K, quantized)
        _hist_chunks(tile[0:F, :].astype(jnp.int32), Wv, out_ref, Fc, C=C,
                     K=K, LO=LO, HB=HB, quantized=quantized)
        stg_ref[:, 0:T] = stg_ref[:, T:2 * T]
        stg_ref[:, T:2 * T] = jnp.zeros((stg_ref.shape[0], T), jnp.float32)
        return carry

    # the full tiles, and at the last step the rest (the fill is < 2T)
    fill = fill_ref[0]
    tiles = jnp.where(n == pl.num_programs(ax) - 1, (fill + T - 1) // T,
                      fill // T)
    jax.lax.fori_loop(0, tiles, contract, 0)
    fill_ref[0] = jnp.maximum(fill - tiles * T, 0)


def _wave_scratch(F, C, n_blk):
    """The megakernel's scratch: a block's staged data, the staging
    buffer (two tiles: a block appends at most one more while the fill
    is under a tile), its fill, and the rows' places in their
    sub-tiles."""
    D = _round_up(F + C + 1, 8)
    return [pltpu.VMEM((D, n_blk), jnp.float32),
            pltpu.VMEM((D, 2 * n_blk), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((n_blk // _SUB, _SUB), jnp.int32)]


@functools.partial(jax.jit,
                   static_argnames=("num_slots", "num_bins", "interpret",
                                    "wide_lo", "features"))
def wave_pass_pallas(
    X_binned_t: jnp.ndarray,   # [F, N] int8/uint8 (feature-major, F <= 32)
    vals: jnp.ndarray,         # [C, N] f32 (bag-masked) or int8 (quantized)
    leaf_of_row: jnp.ndarray,  # [N] int32
    table: jnp.ndarray,        # [T_ROWS, 128] int32 semantic wave table
    num_slots: int,
    num_bins: int,
    interpret: bool = False,
    wide_lo: int = 128,
    cat: jnp.ndarray | None = None,  # [128, 2W] i32 (wave_cat_words)
    in_bag: jnp.ndarray | None = None,  # [N'] in-bag rows (with cat)
    features: int = 0,
) -> tuple[jnp.ndarray, ...]:
    """Fused wave pass: returns (new_leaf_of_row [N] i32,
    hist [K, C, F, num_bins], rows i32), and on a categorical table
    (new_leaf_of_row, hist, counts, rows) with counts [K] i32: each
    slot's in-bag rows (``in_bag`` > 0), which the grower takes as the
    smaller children's exact counts. ``rows`` is the number of rows that
    reached the histogram's contraction: those in a candidate's smaller
    child (the kernel compacts them first). X/vals may be pre-padded (F
    to 32, rows to a block multiple) by the caller so the pad/convert
    cost is paid once per tree instead of once per wave; `leaf_of_row`
    keeps the true row count and the outputs are sliced to it. `wide_lo`
    selects the wide-bin (>128) hi/lo decomposition (_compute_dims); the
    VMEM footprint of the output block is identical for either choice
    (HB*LO = B), so the caller's K cap is unaffected. `cat`, on a
    categorical table, is every entry's bitset (wave_cat_words); without
    it the kernel is the numeric one, operand for operand. `features`,
    with `cat`, is the table's own column count when X is pre-padded
    past it: the histogram and the row's column pick cover those rows of
    X alone (a numeric table's kernel covers every row of X). With `cat`
    the rows are summed in partitions (wave_parts)."""
    F, NX = X_binned_t.shape
    C = vals.shape[0]
    N = leaf_of_row.shape[0]
    K = num_slots
    quantized = vals.dtype == jnp.int8
    B, LO, HB = _compute_dims(num_bins, wide_lo)
    assert F <= 32, "wave megakernel requires F <= 32 storage columns"
    Fp = 32
    n_blk = N_BLK if NX >= N_BLK else max(_round_up(NX, 256), 256)
    P = wave_parts(NX) if cat is not None else 1
    Np = _round_up(NX, n_blk * P)
    X = X_binned_t.astype(jnp.int8)
    if Fp != F or Np != NX:
        X = jnp.pad(X, ((0, Fp - F), (0, Np - NX)))
    if cat is not None and features:
        F = features
    rows = HB * C * K
    Fc = _feat_chunk(F, LO, rows)
    Fh = _round_up(F, Fc)
    v = vals if quantized else vals.astype(jnp.float32)
    if v.shape[1] != Np:
        v = jnp.pad(v, ((0, 0), (0, Np - v.shape[1])))
    lor = leaf_of_row.astype(jnp.int32)
    if Np != N:
        lor = jnp.pad(lor, (0, Np - N), constant_values=-1)
    tblp = _pack_wave_table(table)
    nl0 = table[_T_NL0, 0:1].astype(jnp.int32)

    out_dtype = jnp.int32 if quantized else jnp.float32
    W, cat_in, cat_spec = _cat_operand(cat)
    kernel = functools.partial(_wave_kernel, K=K, C=C, LO=LO, HB=HB, F=F,
                               Fc=Fc, quantized=quantized, **W)
    name = kernel_name("wave_pass", k=K, b=num_bins, lo=LO, q=quantized,
                       **({"f": F, "cat": True} if W else {}))
    if W:
        newlor, out, cnt, n_rows = _wave_pass_cat(
            kernel, name, interpret, X, v, lor, tblp, nl0, cat_in, cat_spec,
            in_bag, Fp=Fp, n_blk=n_blk, P=P, C=C, K=K, rows=rows, Fh=Fh,
            LO=LO, out_dtype=out_dtype, F=F)
        hist = _unflatten_hist(out, K, C, F, Fh, LO, HB, num_bins)
        return newlor[0, :N], hist, cnt, n_rows[0]
    grid = (Np // n_blk,)
    newlor, out, n_rows = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((Fp, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((128, 8), lambda n: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, Fh * LO), lambda n: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Np), jnp.int32),
            jax.ShapeDtypeStruct((rows, Fh * LO), out_dtype),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=_wave_scratch(F, C, n_blk),
        name=name,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=HIST_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * K * C * Fh * Np * B,
            bytes_accessed=Fp * Np + (C * 4 + 8) * Np + rows * Fh * LO * 4,
            transcendentals=0,
        ),
    )(X, v, lor[None, :], tblp, nl0)

    hist = _unflatten_hist(out, K, C, F, Fh, LO, HB, num_bins)
    return newlor[0, :N], hist, n_rows[0]


# Row partitions of a categorical table's wave pass. A histogram bin's
# float32 sum over 100M rows, one row block at a time, drifts: adding a
# block's few units to a sum of 10^5 drops their low bits, one way more
# often than the other (a simulated hub airport's hessian bin read -28.5
# of 367,743 after 48,828 blocks). The drift stays in every larger child
# (its histogram is the parent's less the smaller's), where the bins are
# hundreds of times smaller, and a categorical search sorts those bins by
# G / (H + cat_smooth): the order, and the left set, move. Summing P
# partitions of the rows apart and then the P partial histograms shrinks
# the accumulator, and with it the drift (0.68 at P = 32, simulated).
WAVE_PARTS = 32


def wave_parts(rows: int) -> int:
    """Row partitions of a categorical wave pass over ``rows`` rows."""
    n_blk = N_BLK if rows >= N_BLK else max(_round_up(rows, 256), 256)
    return max(1, min(WAVE_PARTS, -(-rows // n_blk)))


def _wave_pass_cat(kernel, name, interpret, X, v, lor, tblp, nl0, cat_in,
                   cat_spec, in_bag, *, Fp, n_blk, P, C, K, rows, Fh, LO,
                   out_dtype, F):
    """The categorical megakernel over a (partition, block) grid: each
    partition accumulates its own histogram and slot counts, summed here
    (wave_parts)."""
    Np = X.shape[1]
    inner = Np // n_blk // P
    inb = (in_bag > 0).astype(jnp.int8)
    if inb.shape[0] != Np:
        inb = jnp.pad(inb[:Np], (0, Np - min(inb.shape[0], Np)))

    def rows_of(o, i):
        return (0, o * inner + i)

    def whole(o, i):
        return (0, 0)

    newlor, out, cnt, n_rows = pl.pallas_call(
        kernel,
        grid=(P, inner),
        in_specs=[
            pl.BlockSpec((Fp, n_blk), rows_of, memory_space=pltpu.VMEM),
            pl.BlockSpec((C, n_blk), rows_of, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_blk), rows_of, memory_space=pltpu.VMEM),
            pl.BlockSpec((128, 8), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(cat_spec[0].block_shape, whole,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_blk), rows_of, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, n_blk), rows_of, memory_space=pltpu.VMEM),
            pl.BlockSpec((None, rows, Fh * LO), lambda o, i: (o, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, K, 128), lambda o, i: (o, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Np), jnp.int32),
            jax.ShapeDtypeStruct((P, rows, Fh * LO), out_dtype),
            jax.ShapeDtypeStruct((P, K, 128), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=_wave_scratch(F, C, n_blk),
        name=name,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=HIST_VMEM_LIMIT_BYTES),
    )(X, v, lor[None, :], tblp, nl0, *cat_in, inb[None, :])
    return (newlor, jnp.sum(out, axis=0), jnp.sum(cnt[:, :, 0], axis=0),
            n_rows)


def _wave_apply_kernel(dec_ref, lor_ref, tbl_ref, nl0_ref, newlor_ref,
                       slot_ref):
    """Grid (N_blocks,). dec_ref [128, R] i8: bit0 = apply go-left under
    entry k's split, bit1 = row lands in entry k's SMALLER child;
    lor_ref [1, R]; tbl_ref [128, 8] i32 (col 0 applied leaf id, col 2
    candidate leaf id; -1 = inactive); nl0_ref [1] i32 SMEM.
    Outputs new_lor [1, R] and candidate slot ids [1, R] (-1 = none).

    The decisions were precomputed OUTSIDE (XLA elementwise on extracted
    feature columns), which is what makes this kernel independent of the
    feature count, categorical bitsets, and EFB bundle unpacking — it
    only resolves leaf membership."""
    R = lor_ref.shape[1]
    K = 128
    dec = dec_ref[...].astype(jnp.int32)                   # [128, R]
    lor = lor_ref[0, :]
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (K, R), 0)

    mA = lor[None, :] == tbl_ref[:, 0:1]                   # [128, R]
    glA = jnp.sum(jnp.where(mA, dec & 1, 0), axis=0)       # [R]
    inA = jnp.sum(jnp.where(mA, 1, 0), axis=0)
    slotA = jnp.sum(jnp.where(mA, iota_k, 0), axis=0)
    nl0 = nl0_ref[0]
    new_lor = jnp.where((inA == 1) & (glA == 0), nl0 + slotA, lor)
    newlor_ref[0, :] = new_lor

    mC = new_lor[None, :] == tbl_ref[:, 2:3]               # [128, R]
    in_small = jnp.sum(jnp.where(mC, (dec >> 1) & 1, 0), axis=0)
    slotC = jnp.sum(jnp.where(mC, iota_k, 0), axis=0)
    inC = jnp.sum(jnp.where(mC, 1, 0), axis=0)
    slot_ref[0, :] = jnp.where((inC == 1) & (in_small == 1), slotC, -1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wave_apply_pallas(
    dec: jnp.ndarray,          # [128, N] i8 decision bits per (entry, row)
    leaf_of_row: jnp.ndarray,  # [N] int32
    table: jnp.ndarray,        # [T_ROWS, 128] int32 semantic wave table
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Split application + candidate smaller-child slot assignment for
    the EFB wave path: returns (new_leaf_of_row [N],
    slot_small [N] with -1 = no candidate). The histogram then runs as a
    separate build_histogram_slots_pallas pass (whose grid feature-blocks
    arbitrary F)."""
    N = leaf_of_row.shape[0]
    n_blk = N_BLK if N >= N_BLK else max(_round_up(N, 256), 256)
    Np = _round_up(N, n_blk)
    d = dec.astype(jnp.int8)
    if d.shape[1] != Np:
        d = jnp.pad(d, ((0, 0), (0, Np - d.shape[1])))
    lor = leaf_of_row.astype(jnp.int32)
    if Np != N:
        lor = jnp.pad(lor, (0, Np - N), constant_values=-1)
    t = table.astype(jnp.int32)
    tblp = jnp.stack([t[_T_APP_LEAF], t[_T_APP_LEAF] * 0,
                      t[_T_CAND_LEAF], t[_T_APP_LEAF] * 0,
                      t[_T_APP_LEAF] * 0, t[_T_APP_LEAF] * 0,
                      t[_T_APP_LEAF] * 0, t[_T_APP_LEAF] * 0], axis=1)
    nl0 = t[_T_NL0, 0:1]
    newlor, slot = pl.pallas_call(
        _wave_apply_kernel,
        grid=(Np // n_blk,),
        in_specs=[
            pl.BlockSpec((128, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((128, 8), lambda n: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Np), jnp.int32),
            jax.ShapeDtypeStruct((1, Np), jnp.int32),
        ],
        name=kernel_name("wave_apply"),
        interpret=interpret,
    )(d, lor[None, :], tblp, nl0)
    return newlor[0, :N], slot[0, :N]


# ---- wave_apply with the go-left decision made in the kernel -------------
# Numeric, unbundled tables past the megakernel's 32 columns. The matrix
# route above gets every row's decision under every table entry from XLA,
# a [128, N] int8 array (two row gathers of X, an `or`, a pad) that the
# kernel reads back. Here the kernel reads the binned rows themselves and
# decides each row under its OWN entry only, as the megakernel's
# _wave_logic does: the entry's fields arrive by a masked [128, R] pick
# of the packed table, the row's bin of the entry's feature by a masked
# reduction over 32-feature tiles (so the working set is [32, R] at any
# F). The table is _pack_wave_table's (its 5-bit feature field ignored)
# plus the whole feature index in col 4 (applied) and col 5 (candidate).

def _wave_decide_kernel(x_ref, lor_ref, tbl_ref, nl0_ref, *refs, W=0):
    """Grid (N_blocks,). x_ref [F_pad, R] uint8 or int8 binned rows; lor_ref
    [1, R]; tbl_ref [128, 8] i32 packed; nl0_ref [1] i32 SMEM; on a
    categorical table (W > 0) cat_ref [8W, 128] f32 bitset bytes. Outputs
    new_lor [1, R] and the candidate slot of each row's smaller child
    [1, R] (-1 = none). Rows of x_ref past F, and columns past N in the
    last block, hold whatever the block's copy left there: no entry
    names such a feature, and such a row's results are not written."""
    cat_ref, (newlor_ref, slot_ref) = (refs[0], refs[1:]) if W \
        else (None, refs)
    R = lor_ref.shape[1]
    iota_t = jax.lax.broadcasted_iota(jnp.int32, (F_BLK, R), 0)

    def go_left(p, feat, byts=None):
        # one tile holds the row's feature, the others add 0 (an
        # unmatched row reads feature 0, unused); the signed byte is
        # masked after the sum
        def tile(t, col):
            f0 = pl.multiple_of(t * F_BLK, F_BLK)
            xx = x_ref[pl.ds(f0, F_BLK), :].astype(jnp.int32)
            return col + jnp.sum(
                jnp.where((feat - f0)[None, :] == iota_t, xx, 0), axis=0)
        col = jax.lax.fori_loop(0, x_ref.shape[0] // F_BLK, tile,
                                jnp.zeros((R,), jnp.int32)) & 0xFF
        thr = (p >> 5) & 0xFF
        dl = (p >> 13) & 1
        mb = (p >> 14) & 0x1FF
        if byts is not None:
            return jnp.where(col == mb, dl, _bit_of(byts, col))
        return jnp.where(col == mb, dl, (col <= thr).astype(jnp.int32))

    lor = lor_ref[0, :]
    mA = lor[None, :] == tbl_ref[:, 0:1]                   # [128, R]
    pA = _masked_pick(mA, tbl_ref[:, 1:2])
    glA = go_left(pA, _masked_pick(mA, tbl_ref[:, 4:5]),
                  _pick_bytes(mA, cat_ref, 0, W) if W else None)
    new_lor = jnp.where((((pA >> 24) & 1) == 1) & (glA == 0),
                        nl0_ref[0] + ((pA >> 25) & 127), lor)
    newlor_ref[0, :] = new_lor

    mC = new_lor[None, :] == tbl_ref[:, 2:3]               # [128, R]
    pC = _masked_pick(mC, tbl_ref[:, 3:4])
    glC = go_left(pC, _masked_pick(mC, tbl_ref[:, 5:6]),
                  _pick_bytes(mC, cat_ref, 4 * W, W) if W else None)
    in_small = (((pC >> 24) & 1) == 1) & (glC == ((pC >> 23) & 1))
    slot_ref[0, :] = jnp.where(in_small, (pC >> 25) & 127, -1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wave_decide_pallas(
    X_binned_t: jnp.ndarray,   # [F, N] int8/uint8 (feature-major)
    leaf_of_row: jnp.ndarray,  # [N] int32
    table: jnp.ndarray,        # [T_ROWS, 128] int32 semantic wave table
    interpret: bool = False,
    cat: jnp.ndarray | None = None,  # [128, 2W] i32 (wave_cat_words)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """wave_apply_pallas's results, (new_leaf_of_row [N], slot_small [N]
    with -1 = no candidate), with each row's decision made in the kernel
    from the binned rows: unbundled storage only (the EFB unpack stays on
    the matrix route); a categorical table passes `cat`, every entry's
    bitset (wave_cat_words). The rows are read where they lie: the
    blocks' edges past F and N are the grid's, so nothing of size [F, N]
    is copied."""
    F, N = X_binned_t.shape
    Fp = _round_up(F, F_BLK)
    # rows a block: fewer than N_BLK where the [F_pad, R] block would pass
    # 4 MiB (F_pad > 2048), so its two buffers fit scoped VMEM at any F
    n_blk = min(N_BLK if N >= N_BLK else max(_round_up(N, 256), 256),
                max(128, (4 << 20) // Fp // 128 * 128))
    t = table.astype(jnp.int32)
    tblp = _pack_wave_table(t).at[:, 4].set(t[_T_APP_FEAT]) \
        .at[:, 5].set(t[_T_CAND_FEAT])
    W, cat_in, cat_spec = _cat_operand(cat)
    newlor, slot = pl.pallas_call(
        functools.partial(_wave_decide_kernel, **W),
        grid=(pl.cdiv(N, n_blk),),
        in_specs=[
            pl.BlockSpec((Fp, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((128, 8), lambda n: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ] + cat_spec,
        out_specs=[
            pl.BlockSpec((1, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, N), jnp.int32),
            jax.ShapeDtypeStruct((1, N), jnp.int32),
        ],
        name=kernel_name("wave_apply", f=Fp, cat=bool(W)),
        interpret=interpret,
    )(X_binned_t, leaf_of_row.astype(jnp.int32)[None, :], tblp,
      t[_T_NL0, 0:1], *cat_in)
    return newlor[0], slot[0]


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "interpret", "features"))
def wave_relabel_pallas(
    X_binned_t: jnp.ndarray,   # [F, N] int8/uint8 (feature-major, F <= 32)
    vals: jnp.ndarray,         # [C, N] (unused; kept for a uniform ABI)
    leaf_of_row: jnp.ndarray,  # [N] int32
    table: jnp.ndarray,        # [T_ROWS, 128] int32 semantic wave table
    num_bins: int,
    interpret: bool = False,
    cat: jnp.ndarray | None = None,  # [128, 2W] i32 (wave_cat_words)
    features: int = 0,
) -> jnp.ndarray:
    """Split application only: returns new_leaf_of_row [N] i32. Used for
    a tree's final wave (no candidates left to speculate). `vals` is only
    consulted for its dtype — the kernel streams a [C, 128] stub instead
    of DMAing the real value channels it never reads. `cat` and
    `features` as wave_pass_pallas's."""
    F, NX = X_binned_t.shape
    C = vals.shape[0]
    N = leaf_of_row.shape[0]
    quantized = vals.dtype == jnp.int8
    B, LO, HB = _compute_dims(num_bins)
    assert F <= 32
    Fp = 32
    n_blk = N_BLK if NX >= N_BLK else max(_round_up(NX, 256), 256)
    Np = _round_up(NX, n_blk)
    X = X_binned_t.astype(jnp.int8)
    if Fp != F or Np != NX:
        X = jnp.pad(X, ((0, Fp - F), (0, Np - NX)))
    if cat is not None and features:
        F = features
    v = vals[:, :128]
    lor = leaf_of_row.astype(jnp.int32)
    if Np != N:
        lor = jnp.pad(lor, (0, Np - N), constant_values=-1)
    tblp = _pack_wave_table(table)
    nl0 = table[_T_NL0, 0:1].astype(jnp.int32)
    W, cat_in, cat_spec = _cat_operand(cat)
    kernel = functools.partial(_wave_relabel_kernel, C=C, F=F, HB=HB,
                               quantized=quantized, **W)
    newlor = pl.pallas_call(
        kernel,
        grid=(Np // n_blk,),
        in_specs=[
            pl.BlockSpec((Fp, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, 128), lambda n: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((128, 8), lambda n: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ] + cat_spec,
        out_specs=pl.BlockSpec((1, n_blk), lambda n: (0, n),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.int32),
        name=kernel_name("wave_relabel", b=num_bins, q=quantized,
                         **({"f": F, "cat": True} if W else {})),
        interpret=interpret,
    )(X, v, lor[None, :], tblp, nl0, *cat_in)
    return newlor[0, :N]


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "interpret", "wide_lo"))
def build_histogram_pallas(
    X_binned_t: jnp.ndarray,   # [F, N] int8/uint8 (feature-major)
    vals: jnp.ndarray,         # [C, N] f32 (already masked for leaf/bag)
    num_bins: int,
    interpret: bool = False,
    wide_lo: int = 128,
) -> jnp.ndarray:
    """Single-set histogram on TPU: returns [C, F, num_bins] float32.

    Lowered as the K=1 wave kernel with every row active."""
    N = X_binned_t.shape[1]
    slot = jnp.zeros((N,), jnp.int32)
    out = build_histogram_slots_pallas(X_binned_t, vals, slot, 1, num_bins,
                                       interpret=interpret, wide_lo=wide_lo)
    return out[0]

