"""Device-resident binning: raw f32 rows -> uint8 bin indices on device.

Every other layer of the stack binned on host — ``Dataset`` ingest,
the online window refresh, and (worst) every ``binned``/``compiled``/
fused serving request transited ``BinnedModel.bin_rows``'s per-feature
numpy searchsorted before the device walk. This module packs a frozen
``BinMapper`` set into a padded device bin table and provides a Pallas
bucketize kernel (plus a kernel-true XLA reference that runs anywhere)
mapping raw f32 row blocks to uint8 bins BIT-IDENTICALLY to the host
path, so the bucketize can fuse into the same launch as the tree walk:
one program from raw features to margins (docs/PERF.md §8).

Bit-identity with the host f64 searchsorted comes from one invariant:
for an f32 value ``v`` and an f64 inclusive upper bound ``b``,

    v <= b   <=>   v <= floor32(b)

where ``floor32(b)`` is the largest f32 <= ``b`` (there is no f32
strictly between ``floor32(b)`` and ``b``). So the f64 ``searchsorted
(bounds, v, side="left")`` — the count of bounds strictly below ``v`` —
equals the f32 count of ``floor32(bounds) < v`` exactly, for every f32
``v`` including ±0, subnormals and ±inf. This is the same f32-floored-
threshold trick the raw device walk uses for routing exactness
(docs/PARITY.md). Categorical features compare ``trunc(v)`` against the
mapper's key set (keys refused at pack time unless f32-exact), matching
the host ``astype(int64)`` truncation for every f32 input.

Two table modes mirror the two host semantics:

 * ``mode="train"``  — ``BinMapper.value_to_bin``: categorical NaN /
   negative / unseen values land in bin 0 (the mapper's ``-1`` key),
   used for ``Dataset`` ingest and the online window refresh;
 * ``mode="serve"``  — ``BinnedModel.bin_rows``: categorical NaN /
   negative / unseen values land in the per-feature SENTINEL bin
   (``num_bin``), whose bin-domain bitset bit is never set, and only
   split-used features are binned (others stay 0).

``pack_bin_table`` raises :class:`BinningUnavailable` for anything the
device table cannot represent exactly (bin counts over the uint8 cap,
categorical keys that are not f32-exact); callers fall back to the
host path loudly.

Escape hatches: ``binning_impl=host`` (config) or
``LIGHTGBM_TPU_DISABLE_DEVICE_BINNING=1`` (env, read at resolve time)
force the host path everywhere; ``LIGHTGBM_TPU_PALLAS_INTERPRET=1``
routes the Pallas kernel through the interpreter on any backend (the
parity suites in tests/test_predict_binned.py run there).
"""

from __future__ import annotations

import collections
import functools
import os
from typing import List, NamedTuple, Optional, Sequence

import numpy as np

from ..models.tree import MISSING_NAN
from ..runtime.profiler import count as span_count, span
from ..utils import kernel_name, round_up as _round_up

# meta row layout ([F, 8] f32, one row per feature)
_M_IS_CAT = 0     # 1.0 = categorical feature
_M_CLAMP = 1      # numeric: max bin id after the bound count
_M_NAN_BIN = 2    # numeric: bin id NaN rows take
_M_NAN_KEY = 3    # categorical: key substituted for NaN values
_M_MISS_BIN = 4   # categorical: bin id for unseen/invalid values
_M_NEG_INV = 5    # categorical: 1.0 = negative values are invalid (serve)
_META_COLS = 8

_ROW_TILE = 256           # rows per Pallas grid step (sublane dim of x/out)
_LANES = 128              # bin-table lane quantum
_SUBLANES = 32            # table feature-axis padding quantum

# largest integer magnitude where every int is f32-exact
_F32_EXACT_INT = 1 << 24


class BinningUnavailable(ValueError):
    """The device bin table cannot represent this mapper set exactly
    (see message); callers fall back to host binning."""


class DeviceBinTable(NamedTuple):
    """Packed host-side bin table (plain numpy; upload via jnp.asarray
    at trace time so jit/export fold it in as constants).

    ``table``/``cat_val``/``meta`` are padded to ``[F_pad, B]`` /
    ``[F_pad, 8]`` with inert rows (all-+inf bounds, clamp 0) so the
    Pallas block shapes stay tile-aligned; ``num_features`` is the true
    feature count."""
    table: np.ndarray        # [F_pad, B] f32: floored bounds / cat keys
    cat_val: np.ndarray      # [F_pad, B] f32: cat bin values (0 numeric)
    meta: np.ndarray         # [F_pad, 8] f32 per-feature scalars
    num_features: int
    B: int
    mode: str                # "train" | "serve"


def device_binning_disabled() -> bool:
    """LIGHTGBM_TPU_DISABLE_DEVICE_BINNING=1 forces host binning at
    every site (read at resolve time, like the Pallas kill switch)."""
    return os.environ.get("LIGHTGBM_TPU_DISABLE_DEVICE_BINNING",
                          "").lower() in ("1", "true", "yes")


def resolve_binning_impl(knob: str = "auto") -> str:
    """Resolve the ``binning_impl`` knob to "host" or "device".

    "auto" picks device on TPU backends (and under
    LIGHTGBM_TPU_PALLAS_INTERPRET, the kernel-true CPU mode); host
    elsewhere — the same backend heuristic as the serving engine
    default. ``runtime/autotune.py:autotune_binning_decision`` refines
    "auto" by measurement when autotuning is on."""
    if device_binning_disabled():
        return "host"
    if knob in ("host", "device"):
        return knob
    import jax

    from .histogram import pallas_interpret
    if pallas_interpret() or jax.default_backend() == "tpu":
        return "device"
    return "host"


# ----------------------------------------------------------------------
# packing
# ----------------------------------------------------------------------
def _floor_f32(bounds: np.ndarray) -> np.ndarray:
    """Largest f32 <= each f64 bound: f32 round-to-nearest, then step
    DOWN one ulp wherever rounding went up. ``v <= b  <=>  v <=
    floor32(b)`` for every f32 ``v`` — the routing-exactness identity."""
    b64 = np.asarray(bounds, np.float64)
    b32 = b64.astype(np.float32)
    went_up = b32.astype(np.float64) > b64
    stepped = np.nextafter(b32, np.float32(-np.inf))
    return np.where(went_up, stepped, b32).astype(np.float32)


def pack_bin_table(mappers: Sequence, *, mode: str = "train",
                   num_features: Optional[int] = None,
                   used_features: Optional[Sequence[int]] = None,
                   ) -> DeviceBinTable:
    """Pack a frozen BinMapper list into a :class:`DeviceBinTable`.

    ``mappers`` is indexed by storage column (ingest: the dataset's
    inner mapper order) or by original feature with ``None`` holes
    (serving: pass ``used_features`` — unbinned columns pack as inert
    rows that always produce bin 0, exactly like the host path).
    Raises :class:`BinningUnavailable` when the table cannot reproduce
    the host path bit-for-bit."""
    from ..data.binning import BIN_TYPE_CATEGORICAL
    if mode not in ("train", "serve"):
        raise ValueError(f"unknown bin-table mode {mode!r}")
    F = int(num_features) if num_features is not None else len(mappers)
    used = set(int(f) for f in used_features) \
        if used_features is not None else None

    width = 1
    active: List = [None] * F
    for f in range(F):
        mp = mappers[f] if f < len(mappers) else None
        if mp is None or (used is not None and f not in used) \
                or getattr(mp, "is_trivial", False):
            continue
        if mp.bin_type == BIN_TYPE_CATEGORICAL:
            cap = 255 if mode == "serve" else 256
            if mp.num_bin > cap:
                raise BinningUnavailable(
                    f"feature {f}: {mp.num_bin} categorical bins exceed "
                    f"the uint8 {mode} cap ({cap})")
            keys = sorted(mp.categorical_2_bin)
            for k in keys:
                if abs(int(k)) > _F32_EXACT_INT \
                        or float(np.float32(k)) != float(k):
                    raise BinningUnavailable(
                        f"feature {f}: categorical key {k} is not "
                        f"f32-exact; device binning cannot match the "
                        f"host int64 compare")
            width = max(width, len(keys))
        else:
            if mp.num_bin > 256:
                raise BinningUnavailable(
                    f"feature {f}: {mp.num_bin} bins overflow uint8 "
                    f"storage")
            width = max(width, len(mp.bin_upper_bound))
        active[f] = mp

    B = max(_round_up(width, _LANES), _LANES)
    F_pad = max(_round_up(F, _SUBLANES), _SUBLANES)
    table = np.full((F_pad, B), np.inf, np.float32)
    cat_val = np.zeros((F_pad, B), np.float32)
    meta = np.zeros((F_pad, _META_COLS), np.float32)

    for f, mp in enumerate(active):
        if mp is None:
            continue                      # inert: count 0, clamp 0 -> bin 0
        if mp.bin_type == BIN_TYPE_CATEGORICAL:
            keys = sorted(mp.categorical_2_bin)
            vals = [mp.categorical_2_bin[k] for k in keys]
            table[f, :] = np.nan          # NaN pad: never equal to any vi
            table[f, :len(keys)] = np.asarray(keys, np.float32)
            cat_val[f, :len(vals)] = np.asarray(vals, np.float32)
            meta[f, _M_IS_CAT] = 1.0
            if mode == "serve":
                meta[f, _M_NAN_KEY] = -2.0        # matches no key
                meta[f, _M_MISS_BIN] = float(mp.num_bin)   # sentinel
                meta[f, _M_NEG_INV] = 1.0
            else:
                meta[f, _M_NAN_KEY] = -1.0        # the mapper's NaN key
                meta[f, _M_MISS_BIN] = 0.0
        else:
            ub = np.asarray(mp.bin_upper_bound, np.float64)
            if mp.missing_type == MISSING_NAN:
                bounds = ub[:-1]          # exclude the NaN sentinel bound
                meta[f, _M_CLAMP] = float(mp.num_bin - 2)
                meta[f, _M_NAN_BIN] = float(mp.num_bin - 1)
            else:
                bounds = ub
                meta[f, _M_CLAMP] = float(mp.num_bin - 1)
                # NaN takes the bin of 0.0 (the host where(nan, 0.0, v))
                meta[f, _M_NAN_BIN] = float(
                    mp.value_to_bin(np.array([np.nan]))[0])
            table[f, :len(bounds)] = _floor_f32(bounds)
    return DeviceBinTable(table=table, cat_val=cat_val, meta=meta,
                          num_features=F, B=B, mode=mode)


def stack_bin_tables(tables: Sequence[DeviceBinTable]) -> DeviceBinTable:
    """Stack per-tenant serve tables into one ``[C, F_pad, B]`` super
    table (cross-tenant fused drain, export/fusion.py): every table is
    re-padded to the common feature/bin width; tenant columns beyond a
    tenant's own feature count are inert (bin 0, matching the fused
    supertensor's zero-padded uint8 columns)."""
    F = max(t.num_features for t in tables)
    F_pad = max(t.table.shape[0] for t in tables)
    B = max(t.B for t in tables)
    tab = np.full((len(tables), F_pad, B), np.inf, np.float32)
    cv = np.zeros((len(tables), F_pad, B), np.float32)
    meta = np.zeros((len(tables), F_pad, _META_COLS), np.float32)
    for c, t in enumerate(tables):
        if t.mode != "serve":
            raise ValueError("stack_bin_tables expects serve-mode tables")
        fp, b = t.table.shape
        # NaN-padded categorical rows must keep NaN in the widened lanes
        pad = np.where(np.isnan(t.table[:, :1]), np.nan, np.inf)
        tab[c, :fp, :] = pad
        tab[c, :fp, :b] = t.table
        cv[c, :fp, :b] = t.cat_val
        meta[c, :fp, :] = t.meta
    return DeviceBinTable(table=tab, cat_val=cv, meta=meta,
                          num_features=F, B=B, mode="serve")


# ----------------------------------------------------------------------
# device compute: XLA reference (kernel-true) + Pallas kernel
# ----------------------------------------------------------------------
def _bin_block(x, tab, cv, meta):
    """The bucketize math for one block — shared verbatim by the Pallas
    kernel body and the stacked fleet variant, so the two cannot drift.
    ``x`` [..., R, 1] f32 values; ``tab``/``cv`` [..., 1, B]; ``meta``
    [..., 1, 8]; returns [..., R, 1] f32 bin ids. Every operand keeps
    its two minor axes (rows on sublanes, bins on lanes), which is what
    lets Mosaic tile the body; every op is an exact predicate or a
    small-int f32 sum, so the result is bit-identical across
    backends."""
    import jax.numpy as jnp

    is_cat = meta[..., _M_IS_CAT:_M_IS_CAT + 1]
    clamp = meta[..., _M_CLAMP:_M_CLAMP + 1]
    nan_bin = meta[..., _M_NAN_BIN:_M_NAN_BIN + 1]
    nan_key = meta[..., _M_NAN_KEY:_M_NAN_KEY + 1]
    miss_bin = meta[..., _M_MISS_BIN:_M_MISS_BIN + 1]
    neg_inv = meta[..., _M_NEG_INV:_M_NEG_INV + 1]

    nanm = x != x                                         # [..., R, 1]
    # numeric: count of floored bounds strictly below v == f64
    # searchsorted(side="left"), then the inclusive-bound clamp
    lt = tab < x                                          # [..., R, B]
    cnt = jnp.sum(lt.astype(jnp.float32), axis=-1, keepdims=True)
    num_out = jnp.minimum(cnt, clamp)
    num_out = jnp.where(nanm, nan_bin, num_out)
    # categorical: trunc(v) == host astype(int64) for every f32 v;
    # NaN (and, serve mode, negatives) substitute a never-matching key
    vi = jnp.trunc(x)
    vi = jnp.where(nanm, nan_key, vi)
    vi = jnp.where((x < 0) & (neg_inv > 0), jnp.float32(-2.0), vi)
    eq = tab == vi                                        # [..., R, B]
    hit = jnp.sum(eq.astype(jnp.float32), axis=-1, keepdims=True)
    catv = jnp.sum(jnp.where(eq, cv, jnp.float32(0.0)), axis=-1,
                   keepdims=True)
    cat_out = jnp.where(hit > 0, catv, miss_bin)
    return jnp.where(is_cat > 0, cat_out, num_out)


def _bucketize_kernel(x_ref, tab_ref, cv_ref, meta_ref, out_ref, *, F):
    """Pallas body: one [R, F] row tile (the caller's own row-major
    layout) against the full bin table. fori over features; per feature
    the column is lifted out by a masked lane reduction (a NaN/inf value
    survives the sum: every other lane adds an exact 0), binned as an
    [R, B] predicate block on the VPU (B rides the 128-lane axis,
    reduced along bins), and dropped into its output lane by a select
    against the same lane iota. The result leaves as int32 — Mosaic has
    no f32 -> 8-bit narrowing — and the wrapper narrows to uint8."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    x = x_ref[...]                                        # [R, F] f32
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)

    def body(f, acc):
        here = lane == f
        col = jnp.sum(jnp.where(here, x, jnp.float32(0.0)), axis=1,
                      keepdims=True)                      # [R, 1]
        res = _bin_block(col, tab_ref[pl.ds(f, 1), :],
                         cv_ref[pl.ds(f, 1), :],
                         meta_ref[pl.ds(f, 1), :])        # [R, 1]
        return jnp.where(here, res.astype(jnp.int32), acc)

    out_ref[...] = jax.lax.fori_loop(0, F, body,
                                     jnp.zeros(x.shape, jnp.int32))


def _pallas_ok(B: int) -> bool:
    """Pallas bucketize on real TPU backends or under the interpreter;
    XLA reference elsewhere (same env gates as ops/histogram.py)."""
    import jax

    from .histogram import pallas_interpret
    if os.environ.get("LIGHTGBM_TPU_DISABLE_PALLAS", "").lower() \
            in ("1", "true", "yes"):
        return False
    if B > 4096:
        return False
    return pallas_interpret() or jax.default_backend() == "tpu"


def _bucketize_pallas(X, t: DeviceBinTable):
    """X [n, F] f32 -> [n, F] u8 via the Pallas kernel (grid over row
    tiles; the bin table is one VMEM-resident block: F_pad*B*8 bytes,
    ~256 KiB at 256 features x 128 bins — docs/PERF.md §8)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from .histogram import pallas_interpret

    F = t.num_features
    F_pad, B = t.table.shape
    n = X.shape[0]
    n_pad = max(_round_up(n, _ROW_TILE), _ROW_TILE)
    Xp = jnp.pad(X.astype(jnp.float32), ((0, n_pad - n), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_bucketize_kernel, F=F),
        grid=(n_pad // _ROW_TILE,),
        in_specs=[
            pl.BlockSpec((_ROW_TILE, F), lambda i: (i, 0)),
            pl.BlockSpec((F_pad, B), lambda i: (0, 0)),
            pl.BlockSpec((F_pad, B), lambda i: (0, 0)),
            pl.BlockSpec((F_pad, _META_COLS), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((_ROW_TILE, F), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, F), jnp.int32),
        name=kernel_name("bucketize", f=F),
        interpret=pallas_interpret(),
    )(Xp, jnp.asarray(t.table), jnp.asarray(t.cat_val),
      jnp.asarray(t.meta))
    return out[:n].astype(jnp.uint8)


def _bucketize_xla(X, t: DeviceBinTable):
    """Kernel-true XLA reference: an O(log B) lowering of the
    ``_bin_block`` math for backends without the Pallas kernel. The
    numeric bound count and the categorical key probe are the SAME
    lower-bound search on a per-feature-substituted query, so ONE
    branchless binary search (flat cache-resident gathers, no
    transposes) serves both; counts and key hits are small integers
    either way, so the result is bit-identical to the Pallas kernel
    and the host searchsorted — the parity suite
    (tests/test_predict_binned.py) locks the three together. Runs on
    any backend and exports cleanly (the ``bin_and_score`` artifact
    entry point)."""
    import jax.numpy as jnp

    F = t.num_features
    F_pad, B = t.table.shape
    # a packed host table folds in as constants (numpy here); a traced
    # one (bin_rows_device passes the table as arguments) stays traced
    xp = np if isinstance(t.table, np.ndarray) else jnp
    # NaN pads (categorical rows) lift to +inf so every row is sorted
    tabc = jnp.asarray(
        xp.where(xp.isnan(t.table), xp.inf, t.table))[:F]   # [F, B]
    cv = jnp.asarray(t.cat_val)[:F]
    meta = t.meta[:F]
    is_cat = jnp.asarray(meta[None, :, _M_IS_CAT])          # [1, F]
    clamp = jnp.asarray(meta[None, :, _M_CLAMP])
    nan_bin = jnp.asarray(meta[None, :, _M_NAN_BIN])
    nan_key = jnp.asarray(meta[None, :, _M_NAN_KEY])
    miss_bin = jnp.asarray(meta[None, :, _M_MISS_BIN])
    neg_inv = jnp.asarray(meta[None, :, _M_NEG_INV])

    x = X.astype(jnp.float32)                               # [n, F]
    nanm = x != x
    # the substituted query: numeric rows search the raw value (NaN
    # parked on 0, overridden below); categorical rows search the
    # truncated key with the _bin_block NaN / negative substitutions
    vi = jnp.trunc(x)
    vi = jnp.where(nanm, nan_key, vi)
    vi = jnp.where((x < 0) & (neg_inv > 0), jnp.float32(-2.0), vi)
    xq = jnp.where(is_cat > 0, vi,
                   jnp.where(nanm, jnp.float32(0.0), x))

    # branchless lower bound: pos = #(tab[f] < xq) per (row, feature);
    # probes are flat gathers from the [F*B] table (equal-bound
    # duplicates resolve leftmost, matching the predicate-sum count)
    flat = tabc.reshape(-1)
    base = jnp.arange(F, dtype=jnp.int32)[None, :] * B      # [1, F]
    pos = jnp.zeros(x.shape, jnp.int32)
    step = 1
    while step * 2 <= B:
        step *= 2
    while step:
        cand = jnp.minimum(pos + step, B)
        probe = flat[base + cand - 1]
        pos = jnp.where(probe < xq, cand, pos)
        step //= 2

    cnt = pos.astype(jnp.float32)
    num_out = jnp.minimum(cnt, clamp)
    num_out = jnp.where(nanm, nan_bin, num_out)

    posc = base + jnp.minimum(pos, B - 1)
    hit = flat[posc] == xq
    catv = cv.reshape(-1)[posc]
    cat_out = jnp.where(hit, catv, miss_bin)
    out = jnp.where(is_cat > 0, cat_out, num_out)
    return out.astype(jnp.uint8)


def bucketize_rows(X, t: DeviceBinTable, *, impl: str = "auto"):
    """Traced bucketize: X [n, >=F] raw f32 -> [n, F] uint8 bins,
    bit-identical to the host path the table was packed from. Compose
    inside a jit with the tree walk for the one-launch raw->margins
    program (serving/session.py); ``impl`` pins "pallas"/"xla" (the
    exporter needs "xla" for portable StableHLO)."""
    X = X[:, :t.num_features]
    if impl == "auto":
        impl = "pallas" if _pallas_ok(t.B) else "xla"
    if impl == "pallas":
        return _bucketize_pallas(X, t)
    return _bucketize_xla(X, t)


def bucketize_rows_stacked(X, t: DeviceBinTable, tid, *,
                           tile: int = 8):
    """Cross-tenant bucketize for the fused fleet drain: X [n, F_pad]
    raw f32 + tid [n] i32 tenant ids against a ``stack_bin_tables``
    super table. Gathers each row's tenant table per static feature
    tile (bounds the [n, tile, B] intermediate) — all-XLA so it fuses
    into the same program as ``predict_margin_fused``."""
    import jax.numpy as jnp

    F = t.num_features
    tab = jnp.asarray(t.table)                            # [C, F_pad, B]
    cv = jnp.asarray(t.cat_val)
    meta = jnp.asarray(t.meta)
    Xf = X.astype(jnp.float32)
    outs = []
    for f0 in range(0, F, tile):
        f1 = min(f0 + tile, F)
        tab_g = tab[:, f0:f1, :][tid]                     # [n, Ft, B]
        cv_g = cv[:, f0:f1, :][tid]
        meta_g = meta[:, f0:f1, :][tid]                   # [n, Ft, 8]
        # each (row, feature) pair has its own table: R is a singleton
        res = _bin_block(Xf[:, f0:f1, None, None],
                         tab_g[:, :, None, :], cv_g[:, :, None, :],
                         meta_g[:, :, None, :])           # [n, Ft, 1, 1]
        outs.append(res[..., 0, 0].astype(jnp.uint8))
    return jnp.concatenate(outs, axis=1)


# ----------------------------------------------------------------------
# host-side convenience: chunked ingest binning
# ----------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _bin_rows_jit():
    """The ingest program: ONE jitted function of (rows, table, cat_val,
    meta) for the process (jax imported lazily, as everywhere in this
    module). The bin bounds are data: as arguments they stay out of the
    lowered text, so the second dataset of a shape reuses the first's
    executable, in the process and through the persistent compile cache
    (tests/test_compile_reuse.py). The serving programs fold their
    table in on purpose and call ``bucketize_rows`` with the packed
    table itself."""
    import jax

    def bin_rows(Xc, table, cat_val, meta):
        return bucketize_rows(Xc, DeviceBinTable(
            table, cat_val, meta, num_features=Xc.shape[1],
            B=table.shape[1], mode="train"))

    return jax.jit(bin_rows)


# bin_rows_device's row block (one compiled shape for every table of at
# least this many rows) and how many blocks are on the device at once
_BLOCK_ROWS = 65536
_IN_FLIGHT = 3


def bin_rows_device(dest: np.ndarray, data: np.ndarray,
                    columns: Sequence[int], t: DeviceBinTable) -> None:
    """Device ingest: bin the raw float32 ``data[:, columns]`` through the
    device table into ``dest`` (the binned matrix, or a row range of it),
    under the span ``dataset/bucketize``.

    A software pipeline over row blocks of one padded shape (a table
    under one block is one block of its own rounded size). For each
    block: ``fill`` copies its rows and columns from ``data`` into a
    staging buffer (a plain slice where ``columns`` is ``0..F-1``, else
    a take of the columns); ``issue`` uploads the buffer, dispatches the
    ingest program and starts the bins' download, without waiting. At
    most _IN_FLIGHT blocks are on the device: before block b is issued,
    block b - _IN_FLIGHT is finished: ``device`` waits for its bins and
    fetches them, ``store`` writes its rows once into ``dest``. There is
    a staging buffer a block in flight plus one, so a buffer handed to
    the device is refilled only after its block was fetched.
    ``dataset/bucketize`` counts ``blocks``, ``block_rows``,
    ``in_flight`` and ``column_slice`` (1 where the fill was a slice)."""
    import jax

    with span("dataset/bucketize"):
        n, F = data.shape[0], t.num_features
        chunk = max(min(_BLOCK_ROWS, _round_up(n, _ROW_TILE)), _ROW_TILE)
        blocks = -(-n // chunk)
        in_flight = min(blocks, _IN_FLIGHT)
        cols = np.asarray(columns, np.intp)
        column_slice = bool(np.array_equal(cols, np.arange(F)))
        span_count(blocks=blocks, block_rows=chunk, in_flight=in_flight,
                   column_slice=int(column_slice))
        fn = _bin_rows_jit()
        table = jax.device_put((t.table, t.cat_val, t.meta))  # once a call
        bufs = [np.zeros((chunk, F), np.float32)
                for _ in range(in_flight + 1)]
        pending = collections.deque()

        def finish():
            c0, c1, bins = pending.popleft()
            with span("dataset/bucketize/device", bytes_down=bins.nbytes):
                got = np.asarray(bins)
            with span("dataset/bucketize/store"):
                dest[c0:c1] = got[:c1 - c0]

        for b in range(blocks):
            c0 = b * chunk
            c1 = min(c0 + chunk, n)
            buf = bufs[b % len(bufs)]
            with span("dataset/bucketize/fill", rows=c1 - c0):
                if column_slice:
                    buf[:c1 - c0] = data[c0:c1, :F]
                else:
                    # mode "clip": "raise" would stage the take elsewhere
                    np.take(data[c0:c1], cols, axis=1, out=buf[:c1 - c0],
                            mode="clip")
                buf[c1 - c0:] = 0.0
            if len(pending) == in_flight:
                finish()
            with span("dataset/bucketize/issue", bytes_up=buf.nbytes):
                bins = fn(jax.device_put(buf), *table)
                bins.copy_to_host_async()
                pending.append((c0, c1, bins))
        while pending:
            finish()
