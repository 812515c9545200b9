"""Row-wise multi-value histogram construction (docs/PERF.md).

TPU analog of the reference's `MultiValDenseBin` row-wise path
(multi_val_dense_bin.hpp:21): every used feature's bins live in ONE
packed representation with per-feature offsets into a single flat
histogram buffer, and one pass over the rows accumulates a row's FULL
feature set — where the reference's `TrainingShareStates` picks
row-wise vs col-wise by timing (train_share_states.cpp InitTrain),
`runtime/autotune.py:probe_hist_impls` times this path against the
col-wise kernels under ``histogram_impl=auto``.

The col-wise tiered path (`histogram_tiered.py`) launches one kernel
per lane-width class, each sized to the class width {32, 64, 128, 256};
`vals` and `slot` are re-streamed per class and a 33-bin feature still
pays 64 one-hot lanes. This kernel instead:

  * sizes every feature's one-hot at its own 8-aligned width
    (`rw_width`: 33 bins -> 40 columns, not 64),
  * walks the whole storage matrix in ONE launch — the per-feature
    one-hots of a row block are concatenated into a single
    [chunk_cols, R] operand and contracted on the MXU in one
    `W @ oh^T` matmul per column chunk, accumulating into the flat
    per-feature-offset buffer that `split.py:expand_feature_offset_hist`
    already consumes (the same buffer layout the tiered path emits, so
    the split search is untouched),
  * keeps the whole flat [C*K, total] output VMEM-resident across the
    row sweep (grid over N only) — `rowwise_eligible` gates on that
    budget and the dispatcher falls back to the col-wise route when a
    wide wave exceeds it.

EFB bundles fold in for free: offsets are per STORAGE column, and a
bundle column is just a storage column with a packed bin count.

Bit-identity contract (same as histogram_tiered.py): a histogram
element is a dot over the same padded row-block order with the same
bf16 one-hot x bf16 value products (or exact s8 x s8 -> s32 in
quantized mode) as the col-wise kernels — pad columns and foreign
features contribute exact zeros — so the row-wise buffer expands to
bit-identical histograms per feature.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import kernel_name, round_up as _round_up
from .histogram_pallas import N_BLK, _make_W

# one MXU contraction per column chunk: the [chunk_cols, R] one-hot
# operand is bounded to 2048 sublanes (8 MB bf16 at R=2048), the same
# budget histogram_pallas._feat_chunk uses
CHUNK_COLS = 2048
# the flat [C*K, total] output block stays VMEM-resident for the whole
# row sweep; same budget as the narrow col-wise path
OUT_VMEM_BYTES = 3_400_000


def rw_width(num_bin: int) -> int:
    """Flat columns a feature owns: its bin count rounded up to the
    8-sublane tile (vs the col-wise lane-width classes 32/64/128/256 —
    the row-wise layout's lane economy on odd widths)."""
    if num_bin > 256:
        raise ValueError(f"num_bin {num_bin} exceeds 256 (8-bit storage)")
    return max(_round_up(int(num_bin), 8), 8)


class RowWisePlan(NamedTuple):
    """Static flat-buffer layout (hashable — jit static arg / lru key).

    ``chunks`` drives the kernel: one MXU contraction per entry,
    ``(col0, cols, runs)`` where ``runs`` is ``((f0, count, width), ...)``
    — maximal groups of consecutive equal-width features (tier-ordered
    storage makes these long). ``col0`` is 128-aligned (chunk tails are
    zero-padded up to the lane tile) so the accumulate is an aligned
    lane slice."""
    chunks: tuple    # ((col0, cols, ((f0, count, width), ...)), ...)
    offsets: tuple   # [F] per-feature start column in the flat buffer
    widths: tuple    # [F] per-feature flat columns owned (rw_width)
    total: int       # flat buffer width (128-aligned)


@functools.lru_cache(maxsize=256)
def build_rowwise_plan(feature_num_bins: tuple) -> RowWisePlan:
    """Lay out the flat multi-value buffer: per-feature 8-aligned widths
    packed into 128-aligned column chunks of <= CHUNK_COLS sublanes.

    Keep the arithmetic in lockstep with the numpy twin
    `data/dataset.py:_multival_layout` (duplicated there so data loading
    never imports jax; tests pin the two equal)."""
    offsets, widths, chunks = [], [], []
    runs: list = []
    col0 = used = 0
    for f, nb in enumerate(feature_num_bins):
        w = rw_width(int(nb))
        if used and used + w > CHUNK_COLS:
            chunks.append((col0, _round_up(used, 128),
                           tuple(tuple(r) for r in runs)))
            col0 += _round_up(used, 128)
            runs, used = [], 0
        if runs and runs[-1][2] == w:
            runs[-1][1] += 1
        else:
            runs.append([f, 1, w])
        offsets.append(col0 + used)
        widths.append(w)
        used += w
    if runs:
        chunks.append((col0, _round_up(used, 128),
                       tuple(tuple(r) for r in runs)))
        col0 += _round_up(used, 128)
    return RowWisePlan(tuple(chunks), tuple(offsets), tuple(widths), col0)


def rowwise_eligible(plan: RowWisePlan, C: int, K: int) -> bool:
    """Whole-flat-output VMEM residency gate: wide waves (large K) at
    wide totals fall back to the col-wise route at the dispatcher."""
    return plan.total > 0 and C * K * plan.total * 4 <= OUT_VMEM_BYTES


def _mv_accum(xx_all, W, out_ref, *, chunks, quantized):
    """Shared multi-value contraction body: one MXU matmul per column
    chunk, accumulating into the VMEM-resident flat buffer. `xx_all`
    is the [F, R] int32 bin-code block — materialized from the plain
    int8 storage OR nibble-unpacked from the 4-bit pack; either way the
    codes (and thus every one-hot product) are identical, which is what
    makes the packed kernel bit-identical by construction."""
    R = xx_all.shape[1]
    w_dtype = jnp.int8 if quantized else jnp.bfloat16
    acc = jnp.int32 if quantized else jnp.float32
    for (col0, cols, runs) in chunks:
        # concatenated multi-value one-hot: run (f0, m, w) owns sublanes
        # [off, off + m*w) with oh[off + j*w + b, r] = (bin[f0+j, r] == b)
        # — every feature at ITS width, one compare per run
        parts = []
        used = 0
        for (f0, m, w) in runs:
            iota3 = jax.lax.broadcasted_iota(jnp.int32, (m, w, R), 1)
            # slice, then add the bin axis: a combined [f0:f0+m, None, :]
            # index traces to a gather, which Mosaic refuses
            parts.append((xx_all[f0:f0 + m][:, None, :] == iota3)
                         .reshape(m * w, R).astype(w_dtype))
            used += m * w
        if used < cols:
            parts.append(jnp.zeros((cols - used, R), w_dtype))
        oh = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
        part = jax.lax.dot_general(
            W, oh, (((1,), (1,)), ((), ())),
            preferred_element_type=acc)                 # [C*K, cols]
        out_ref[:, col0:col0 + cols] += part


def _rowwise_kernel(x_ref, v_ref, s_ref, out_ref, *, K, C, chunks,
                    quantized):
    """Grid (N_blocks,): the flat [C*K, total] output block is resident
    across the whole row sweep.

    x_ref  [F, R]   int8        binned storage columns (this row block)
    v_ref  [C, R]   f32 / int8  value channels (bag-masked)
    s_ref  [1, R]   int32       slot id per row; outside [0, K) = none
    out_ref[C*K, total]         f32 / int32 flat per-feature-offset buffer
    """
    n = pl.program_id(0)

    @pl.when(n == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    R = v_ref.shape[1]
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (K, R), 0)
    oh_slot = s_ref[0:1, :] == iota_k                   # [K, R]
    W = _make_W(v_ref[...], oh_slot, C, K, quantized)   # [C*K, R]
    # storage rides in as int8 (Mosaic-safe narrow load); mask the sign
    # extension away so 256-bin columns compare as unsigned 0..255
    xx_all = x_ref[...].astype(jnp.int32) & 255
    _mv_accum(xx_all, W, out_ref, chunks=chunks, quantized=quantized)


@functools.partial(jax.jit, static_argnames=("num_slots", "plan",
                                             "interpret"))
def build_histogram_slots_rowwise_flat(
    X_binned_t: jnp.ndarray,   # [F, N] int8/uint8 (storage order)
    vals: jnp.ndarray,         # [C, N] f32 (bag-masked) or int8 (quantized)
    slot: jnp.ndarray,         # [N] int32
    num_slots: int,
    plan: RowWisePlan,
    interpret: bool = False,
) -> jnp.ndarray:
    """Flat row-wise wave histogram: returns [K, C, total] (f32, or
    int32 for quantized vals) — ONE kernel launch covering every
    storage column at its own width."""
    F, N = X_binned_t.shape
    C = vals.shape[0]
    K = num_slots
    assert len(plan.widths) == F
    quantized = vals.dtype == jnp.int8
    rows = C * K
    n_blk = N_BLK if N >= N_BLK else max(_round_up(N, 256), 256)
    Np = _round_up(N, n_blk)
    X = X_binned_t.astype(jnp.int8)
    v = vals if quantized else vals.astype(jnp.float32)
    s = slot.astype(jnp.int32)
    if Np != N:
        X = jnp.pad(X, ((0, 0), (0, Np - N)))
        v = jnp.pad(v, ((0, 0), (0, Np - N)))
        s = jnp.pad(s, (0, Np - N), constant_values=-1)
    out_dtype = jnp.int32 if quantized else jnp.float32
    kernel = functools.partial(_rowwise_kernel, K=K, C=C,
                               chunks=plan.chunks, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid=(Np // n_blk,),
        in_specs=[
            pl.BlockSpec((F, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rows, plan.total), lambda n: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, plan.total), out_dtype),
        name=kernel_name("hist_rowwise", k=K, q=quantized),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * plan.total * Np,
            bytes_accessed=F * Np + (C * 4 + 4) * Np
            + rows * plan.total * 4,
            transcendentals=0,
        ),
    )(X, v, s[None, :])
    # W is channel-major ([c*K + k, :]) like the col-wise kernels
    return out.reshape(C, K, plan.total).transpose(1, 0, 2)


def build_histogram_slots_rowwise(
    X_binned_t: jnp.ndarray,
    vals: jnp.ndarray,
    slot: jnp.ndarray,
    num_slots: int,
    num_bins: int,
    plan: RowWisePlan,
    interpret: bool = False,
) -> jnp.ndarray:
    """Row-wise wave histogram expanded back to the uniform grid:
    [K, C, F, num_bins], drop-in for the growers."""
    from .split import expand_feature_offset_hist
    flat = build_histogram_slots_rowwise_flat(
        X_binned_t, vals, slot, num_slots, plan, interpret=interpret)
    return expand_feature_offset_hist(flat, plan.offsets, plan.widths,
                                      num_bins)


def build_histogram_rowwise(
    X_binned_t: jnp.ndarray,
    vals: jnp.ndarray,
    num_bins: int,
    plan: RowWisePlan,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-set row-wise histogram: [C, F, num_bins] (K=1 wrapper)."""
    slot = jnp.zeros((X_binned_t.shape[1],), jnp.int32)
    out = build_histogram_slots_rowwise(X_binned_t, vals, slot, 1,
                                        num_bins, plan,
                                        interpret=interpret)
    return out[0]


# ---------------------------------------------------------------------------
# 4-bit packed storage (histogram_impl="rowwise_packed", docs/PERF.md)
#
# dense_wide / sparse_onehot shapes are dominated by many narrow columns
# (one-hot expansions bin to 2-3 bins; EFB bundles of them stay under 16)
# whose int8 storage wastes half its bits. Pack TWO <=16-bin storage
# columns per byte — lo nibble = earlier column, hi nibble = later — so
# the binned operand streams at half the HBM bytes, and nibble-unpack
# in-kernel (two VPU shifts/masks) before the SAME `_mv_accum` one-hot
# contraction feeds the MXU. Codes after unpack are identical to the
# unpacked kernel's, so the flat buffer is bit-identical by construction.
# Columns wider than 16 bins ride in an unpacked remainder operand.

class Pack4Plan(NamedTuple):
    """Static nibble layout (hashable — jit static arg / lru key).

    ``pack_pos[f]``: nibble index of storage column f among the packed
    columns (byte ``pack_pos[f] // 2``, shift ``4 * (pack_pos[f] % 2)``),
    or -1 when the column is too wide and lives in the remainder at row
    ``rest_pos[f]``. An odd packed count leaves the last byte's hi
    nibble zero — no ``pack_pos`` points at it, so it is never read."""
    pack_pos: tuple   # [F] nibble index among packed columns, or -1
    rest_pos: tuple   # [F] row in the unpacked remainder, or -1
    n_packed: int     # packable columns (num_bins <= 16)
    n_rest: int       # remainder columns


@functools.lru_cache(maxsize=256)
def build_pack4_plan(feature_num_bins: tuple) -> Pack4Plan:
    """Assign every <=16-bin storage column a nibble, in storage order
    (numpy twin: `data/dataset.py:_pack4` packs host-side from the same
    rule; tests pin the two equal)."""
    pack_pos, rest_pos = [], []
    np_, nr = 0, 0
    for nb in feature_num_bins:
        if int(nb) <= 16:
            pack_pos.append(np_)
            rest_pos.append(-1)
            np_ += 1
        else:
            pack_pos.append(-1)
            rest_pos.append(nr)
            nr += 1
    return Pack4Plan(tuple(pack_pos), tuple(rest_pos), np_, nr)


def pack4_worthwhile(pplan: Pack4Plan) -> bool:
    """Packing saves bytes only when at least one byte carries two
    columns; below that the dispatcher stays on the plain rowwise path."""
    return pplan.n_packed >= 2


def pack4(X_binned_t: jnp.ndarray, pplan: Pack4Plan):
    """Device-side pack: [F, N] int8 storage -> (Xp [n_bytes, N] int8,
    Xu [max(n_rest, 1), N] int8). One elementwise pass; datasets that
    train repeatedly should pack ONCE and reuse (the kernel entry
    accepts prepacked operands) — see `data/dataset.py:_pack4` for the
    host-side twin that packs at load time."""
    import numpy as np
    F, N = X_binned_t.shape
    assert len(pplan.pack_pos) == F
    lo_f = [f for f in range(F) if pplan.pack_pos[f] >= 0
            and pplan.pack_pos[f] % 2 == 0]
    hi_f = [f for f in range(F) if pplan.pack_pos[f] >= 0
            and pplan.pack_pos[f] % 2 == 1]
    rest_f = [f for f in range(F) if pplan.rest_pos[f] >= 0]
    xi = X_binned_t.astype(jnp.int32) & 15
    lo = xi[np.asarray(lo_f, np.int32), :] if lo_f \
        else jnp.zeros((0, N), jnp.int32)
    hi = xi[np.asarray(hi_f, np.int32), :] if hi_f \
        else jnp.zeros((0, N), jnp.int32)
    if lo.shape[0] > hi.shape[0]:        # odd count: hi nibble stays 0
        hi = jnp.pad(hi, ((0, lo.shape[0] - hi.shape[0]), (0, 0)))
    Xp = (lo | (hi << 4)).astype(jnp.int8)
    if rest_f:
        Xu = X_binned_t[np.asarray(rest_f, np.int32), :].astype(jnp.int8)
    else:                                # dummy row keeps BlockSpecs legal
        Xu = jnp.zeros((1, N), jnp.int8)
    return Xp, Xu


def _unpack4_rows(xp, xu, pack_pos, rest_pos):
    """Reassemble the [F, R] int32 bin-code block in STORAGE order from
    the packed nibbles + remainder — static slices only (Mosaic-safe).
    Feeding the result to `_mv_accum` makes the packed kernel's flat
    buffer bit-identical to the unpacked kernel's."""
    xpi = xp.astype(jnp.int32) & 255
    xui = xu.astype(jnp.int32) & 255
    rows = []
    for f in range(len(pack_pos)):
        p = pack_pos[f]
        if p >= 0:
            rows.append((xpi[p // 2:p // 2 + 1, :] >> (4 * (p % 2))) & 15)
        else:
            r = rest_pos[f]
            rows.append(xui[r:r + 1, :])
    return jnp.concatenate(rows, axis=0) if len(rows) > 1 else rows[0]


def _rowwise_packed_kernel(xp_ref, xu_ref, v_ref, s_ref, out_ref, *, K, C,
                           chunks, pack_pos, rest_pos, quantized):
    """`_rowwise_kernel` with the binned operand split into 4-bit packed
    bytes + unpacked remainder; identical contraction body."""
    n = pl.program_id(0)

    @pl.when(n == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    R = v_ref.shape[1]
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (K, R), 0)
    oh_slot = s_ref[0:1, :] == iota_k
    W = _make_W(v_ref[...], oh_slot, C, K, quantized)
    xx_all = _unpack4_rows(xp_ref[...], xu_ref[...], pack_pos, rest_pos)
    _mv_accum(xx_all, W, out_ref, chunks=chunks, quantized=quantized)


@functools.partial(jax.jit, static_argnames=("num_slots", "plan", "pplan",
                                             "interpret"))
def build_histogram_slots_rowwise_packed_flat(
    Xp: jnp.ndarray,           # [n_bytes, N] int8: two nibble columns/byte
    Xu: jnp.ndarray,           # [max(n_rest, 1), N] int8 remainder
    vals: jnp.ndarray,         # [C, N] f32 (bag-masked) or int8 (quantized)
    slot: jnp.ndarray,         # [N] int32
    num_slots: int,
    plan: RowWisePlan,
    pplan: Pack4Plan,
    interpret: bool = False,
) -> jnp.ndarray:
    """Flat row-wise wave histogram from PREPACKED operands: returns
    [K, C, total] like `build_histogram_slots_rowwise_flat`, streaming
    half the binned bytes for the packed columns."""
    N = Xp.shape[1]
    C = vals.shape[0]
    K = num_slots
    F = len(plan.widths)
    assert len(pplan.pack_pos) == F
    assert pplan.n_packed >= 1, "no packable columns: use the plain path"
    assert Xp.shape[0] == (pplan.n_packed + 1) // 2
    quantized = vals.dtype == jnp.int8
    rows = C * K
    n_blk = N_BLK if N >= N_BLK else max(_round_up(N, 256), 256)
    Np = _round_up(N, n_blk)
    Xp = Xp.astype(jnp.int8)
    Xu = Xu.astype(jnp.int8)
    v = vals if quantized else vals.astype(jnp.float32)
    s = slot.astype(jnp.int32)
    if Np != N:
        Xp = jnp.pad(Xp, ((0, 0), (0, Np - N)))
        Xu = jnp.pad(Xu, ((0, 0), (0, Np - N)))
        v = jnp.pad(v, ((0, 0), (0, Np - N)))
        s = jnp.pad(s, (0, Np - N), constant_values=-1)
    out_dtype = jnp.int32 if quantized else jnp.float32
    FP, FU = Xp.shape[0], Xu.shape[0]
    kernel = functools.partial(_rowwise_packed_kernel, K=K, C=C,
                               chunks=plan.chunks,
                               pack_pos=pplan.pack_pos,
                               rest_pos=pplan.rest_pos,
                               quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid=(Np // n_blk,),
        in_specs=[
            pl.BlockSpec((FP, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((FU, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n_blk), lambda n: (0, n),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rows, plan.total), lambda n: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, plan.total), out_dtype),
        name=kernel_name("hist_rowwise_packed", k=K, q=quantized),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * plan.total * Np,
            bytes_accessed=(FP + FU) * Np + (C * 4 + 4) * Np
            + rows * plan.total * 4,
            transcendentals=0,
        ),
    )(Xp, Xu, v, s[None, :])
    return out.reshape(C, K, plan.total).transpose(1, 0, 2)


def build_histogram_slots_rowwise_packed(
    X_binned_t: jnp.ndarray,
    vals: jnp.ndarray,
    slot: jnp.ndarray,
    num_slots: int,
    num_bins: int,
    plan: RowWisePlan,
    pplan: Pack4Plan,
    interpret: bool = False,
) -> jnp.ndarray:
    """Packed row-wise wave histogram expanded back to the uniform grid
    [K, C, F, num_bins] — packs on the fly (correctness/dispatch path;
    benchmarks and repeat-train callers prepack via `pack4` once and
    call the `_flat` entry directly)."""
    from .split import expand_feature_offset_hist
    Xp, Xu = pack4(X_binned_t, pplan)
    flat = build_histogram_slots_rowwise_packed_flat(
        Xp, Xu, vals, slot, num_slots, plan, pplan, interpret=interpret)
    return expand_feature_offset_hist(flat, plan.offsets, plan.widths,
                                      num_bins)


def _build_histogram_slots_rowwise_xla(X_binned_t, vals, slot, num_slots,
                                       plan: RowWisePlan,
                                       rows_per_chunk: int = 8192):
    """Portable XLA lowering of the FLAT row-wise contraction (pinned
    reference for the kernel tests; also what `scripts/bench_rowwise.py`
    times on non-TPU meshes). Same shape of work as the kernel: the
    one-hot has ONE row per flat column — the code of the column's
    owning feature gathered (static index) and compared against the
    column id — so the contraction is a single [K*C, R] @ [R, total]
    matmul per row chunk. MACs scale with the flat total (features at
    their exact 8-aligned widths), not F x lane-width: the layout
    economy is measurable on any backend. int8 vals accumulate exactly
    in int32."""
    F, N = X_binned_t.shape
    C = vals.shape[0]
    K = num_slots
    quantized = vals.dtype == jnp.int8
    acc = jnp.int32 if quantized else jnp.float32
    import numpy as np
    offs = np.asarray(plan.offsets, np.int32)
    # owner[j] = feature whose flat segment holds column j. Chunk-tail
    # pad columns get owner 0: feature 0's codes live in its own
    # segment, never in a pad region, so those one-hot rows are all 0.
    owner = np.zeros(plan.total, np.int32)
    for f, (o, w) in enumerate(zip(plan.offsets, plan.widths)):
        owner[o:o + w] = f
    chunk = min(rows_per_chunk, _round_up(N, 128))
    Np = _round_up(N, chunk)
    if Np != N:
        X_binned_t = jnp.pad(X_binned_t, ((0, 0), (0, Np - N)))
        vals = jnp.pad(vals, ((0, 0), (0, Np - N)))
        slot = jnp.pad(slot, (0, Np - N), constant_values=-1)
    n_chunks = Np // chunk
    # multi-value codes: bin + feature offset — disjoint flat segments
    code = X_binned_t.astype(jnp.int32) + jnp.asarray(offs)[:, None]
    Xc = code.reshape(F, n_chunks, chunk).transpose(1, 0, 2)
    Vc = vals.reshape(C, n_chunks, chunk).transpose(1, 0, 2)
    Sc = slot.reshape(n_chunks, chunk)
    owner_j = jnp.asarray(owner)
    iota_j = jnp.arange(plan.total, dtype=jnp.int32)
    iota_k = jnp.arange(K, dtype=jnp.int32)

    def body(hist, xs):
        cb, vb, sb = xs                              # [F,R], [C,R], [R]
        oh = (cb[owner_j, :] == iota_j[:, None]).astype(acc)  # [total,R]
        oh_slot = (sb[None, :] == iota_k[:, None]).astype(acc)
        w = (oh_slot[:, None, :]
             * vb[None, :, :].astype(acc)).reshape(K * C, -1)
        part = jax.lax.dot_general(w, oh, (((1,), (1,)), ((), ())),
                                   preferred_element_type=acc)
        return hist + part.reshape(K, C, plan.total), None

    hist0 = jnp.zeros((K, C, plan.total), acc)
    hist, _ = jax.lax.scan(body, hist0, (Xc, Vc, Sc))
    return hist
