"""Wave-pipelined leaf-wise tree growth — the TPU throughput grower.

The serial growers (ops/grow.py, ops/grow_fast.py) replay the reference's
one-split-at-a-time loop (SerialTreeLearner::Train,
serial_tree_learner.cpp:222-240): 254 strictly sequential steps per tree,
each step paying a histogram pass plus gathers/scatters that run far below
HBM speed on TPU. This module restructures the SAME algorithm — identical
split mathematics, identical best-first (leaf-wise) order — into batched
"waves" so the device work is a handful of large fused passes per tree:

  1. SPECULATE: take the top-K frontier leaves by cached best-split gain
     whose children's histograms are not yet known, and build ALL their
     smaller-child histograms in ONE slot-kernel pass over the data
     (build_histogram_slots; the per-feature one-hot compare — the
     dominant VPU cost — is shared across the wave). Larger children come
     from the parent-histogram subtraction exactly as in the reference
     (BeforeFindBestSplit, serial_tree_learner.cpp:344).
  2. SEARCH: best splits for all 2K prospective children in one vmapped
     scan (ops/split.py), cached per leaf.
  3. APPLY: a cheap on-device serial loop replays the exact leaf-wise
     priority order (argmax of gain) as far as it can go using only
     leaves whose child data is ready — pure [L]-array bookkeeping, no
     histogram work. When the argmax leaf is not ready (a child created
     in this very wave out-gains the frontier), the wave ends and the
     next wave's pass covers it. Each wave makes >= 1 split of progress;
     typical trees need ~depth + a few waves.
  4. RELABEL: one fused elementwise pass moves rows of all applied splits
     to their new leaves (select over the wave's split features — no
     gather, no scatter, no order permutation).

Order semantics by mode:
  * wave_exact=True: one split applied per wave, chosen by the serial
    growers' priority rule (best frontier gain, serial_tree_learner.cpp:222;
    argmax ties by index). This is an ORDER guarantee, not a bit-identity
    guarantee: histogram entries are (grad, hess) pairs only and per-bin
    counts are cnt_factor-synthesized at search time (synth_count_channel,
    matching the reference's feature_histogram.hpp:529,844), so
    min_data_in_leaf decisions and equal-gain ties on bins within the
    synthesized channel's rounding noise can resolve differently than the
    serial growers' — trees may diverge on such marginal splits
    (docs/PARITY.md "Count-channel synthesis" documents the tolerance).
    Cost: ~O(priority-chain) waves.
  * wave_exact=False (default): each wave applies EVERY ready leaf whose
    gain >= wave_gain_slack * (best frontier gain), in gain order — a
    gain-prioritized batched frontier that approaches strict leaf-wise as
    the slack rises, in ~O(depth) waves. Split mathematics, constraints
    and the leaf budget are identical; only the split ORDER may differ,
    and measured quality matches the serial growers on the parity gates.
Speculation waste is bounded by one wave's worth of histogram slots.

Distributed (tree_learner=data): one collective over the [K,C,F,B] wave
histogram per wave — O(waves) collectives per tree instead of O(L)
(data_parallel_tree_learner.cpp:286-298 does one ReduceScatter per split).
The collective follows `parallel_hist_mode` (docs/PERF.md
§Communication) while feature ownership — each shard searches only the
features it owns, per-wave best-split records merge via
SyncUpGlobalBestSplit (a record gather, or broadcast-free order-encoded
pmax keys under explicit `reduce_scatter`) — stays on in every mode:
`reduce_scatter`/`auto` deliver each shard only its summed feature slice
via psum_scatter; `allreduce` psums the full histogram everywhere and
each shard slices locally (same values bitwise, baseline wire profile),
so the modes grow bit-identical trees. Quantized-gradient histograms
cross the wire as int32-packed-int16 lanes when the static carry bound
holds (parallel/packed.py), halving ICI bytes. Wave selection and the
apply loop depend only on globally-reduced quantities, so every shard
executes identical splits.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .grow import (DeviceTree, GrowConfig, _empty_split_cache, _root_tree,
                   _set_cache)
from .histogram import build_histogram, build_histogram_slots
from ..models.tree import MISSING_NAN, MISSING_ZERO
from .split import (NEG_INF, FeatureMeta, SplitResult, find_best_split,
                    leaf_output, root_totals, synth_count_channel)
from .categorical import find_best_split_categorical


def _wave_buckets(L: int, kcap: int = 128) -> list[int]:
    """Static slot-kernel sizes; the smallest bucket >= wave size is used.
    MXU cost of a slot pass scales linearly with K beyond ~32 (measured
    ~0.22 ms/slot at B=64/C=3/N=4M on v5e), so the ladder uses 1.5x steps
    in the expensive range — a wave of size K pays at most 1.5K slots
    there (pure pow-2 would pay 2K). `kcap` bounds the widest wave (the
    kernel's [HB*C*K, F*LO] f32 output block must stay inside scoped
    VMEM)."""
    kmax = min(kcap, max(L - 1, 1))
    ladder = (1, 2, 4, 8, 16, 32, 48, 64, 96)
    return [k for k in ladder if k < kmax] + [kmax]


def _oh_dot(oh: jnp.ndarray, flat: jnp.ndarray) -> jnp.ndarray:
    """[K, L] one-hot (f32) times [L, D] values; exact for f32 tables and
    for int32 tables (via two 16-bit planes). Precision.HIGHEST is
    REQUIRED: the TPU default runs f32 matmuls as bf16 passes, which
    rounds the 'exact' one-hot products to 8 mantissa bits."""
    dims = (((1,), (0,)), ((), ()))
    hp_ = jax.lax.Precision.HIGHEST
    if flat.dtype == jnp.int32:
        hi = jax.lax.shift_right_arithmetic(flat, 16).astype(jnp.float32)
        lo = (flat & 0xFFFF).astype(jnp.float32)
        ohi = jax.lax.dot_general(oh, hi, dims, precision=hp_,
                                  preferred_element_type=jnp.float32)
        olo = jax.lax.dot_general(oh, lo, dims, precision=hp_,
                                  preferred_element_type=jnp.float32)
        return ohi.astype(jnp.int32) * 65536 + olo.astype(jnp.int32)
    return jax.lax.dot_general(oh, flat, dims, precision=hp_,
                               preferred_element_type=jnp.float32)


def _onehot_gather(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """table [L, ...] gathered at idx [K] -> [K, ...] via a one-hot matmul.

    XLA's native gather runs at ~2 GB/s on this target; a one-hot
    contraction reads the table once at HBM speed on the MXU and is exact
    (each output row sums exactly one 1.0 x value product). Out-of-range
    idx rows return zeros."""
    L = table.shape[0]
    oh = (idx[:, None] == jnp.arange(L, dtype=idx.dtype)[None, :]
          ).astype(jnp.float32)                              # [K, L]
    out = _oh_dot(oh, table.reshape(L, -1))
    return out.reshape((idx.shape[0],) + table.shape[1:])


def _onehot_scatter(table: jnp.ndarray, idx: jnp.ndarray,
                    rows: jnp.ndarray) -> jnp.ndarray:
    """table [L, ...] with rows [K, ...] written at idx [K] (one-hot
    formulation, exact; out-of-range idx rows are dropped). Duplicate
    indices must not occur."""
    L = table.shape[0]
    oh = (idx[:, None] == jnp.arange(L, dtype=idx.dtype)[None, :]
          ).astype(jnp.float32)                              # [K, L]
    keep = (1.0 - jnp.max(oh, axis=0))                       # [L]
    add = _oh_dot(oh.T, rows.reshape(idx.shape[0], -1))
    flat = table.reshape(L, -1) * keep[:, None].astype(table.dtype) + add
    return flat.reshape(table.shape)


class _WaveState(NamedTuple):
    tree: DeviceTree
    leaf_of_row: jnp.ndarray       # [N] i32
    leaf_parent_node: jnp.ndarray  # [L] i32 (-1 = root)
    leaf_is_left: jnp.ndarray      # [L] bool
    leaf_depth: jnp.ndarray        # [L] i32
    leaf_output: jnp.ndarray       # [L] f32
    leaf_sum_g: jnp.ndarray        # [L] f32
    leaf_sum_h: jnp.ndarray        # [L] f32
    hist_cache: jnp.ndarray        # [L, C*F*B] leaf's own histogram, FLAT
    #                                (f32 C=3; exact int32 C=2 quantized)
    small_hist: jnp.ndarray        # [L, C*F*B] pending smaller-child hist
    small_is_left: jnp.ndarray     # [L] bool: which child the above is
    ready: jnp.ndarray             # [L] bool: child hists + searches done
    leaf_min: jnp.ndarray          # [L] f32 monotone output lower bound
    leaf_max: jnp.ndarray          # [L] f32 monotone output upper bound
    leaf_sets: jnp.ndarray         # [L, S] bool satisfiable interaction sets
    best: SplitResult              # [L] per-leaf best split
    best_is_cat: jnp.ndarray       # [L] bool
    best_bitset: jnp.ndarray       # [L, W] u32
    bestl: SplitResult             # [L] best split of the LEFT child
    bestr: SplitResult             # [L] ... and the RIGHT child
    catl: jnp.ndarray              # [L] bool
    catr: jnp.ndarray              # [L] bool
    bitsl: jnp.ndarray             # [L, W] u32
    bitsr: jnp.ndarray             # [L, W] u32
    leaf_forced: jnp.ndarray       # [L] i32 forced-node id (-1 = none)
    best_forced: jnp.ndarray       # [L] bool: best split IS the forced one
    feat_used: jnp.ndarray         # [F] bool: CEGB coupled-penalty state
    fidl: jnp.ndarray              # [L] i32 left child's forced-node id
    fidr: jnp.ndarray              # [L] i32 right child's forced-node id
    bfl: jnp.ndarray               # [L] bool: left child's best is forced
    bfr: jnp.ndarray               # [L] bool: right child's best is forced
    under: jnp.ndarray             # [L, M] i8: 0 = leaf not under node,
    #   1 = in node's left subtree, 2 = right (monotone intermediate)
    stale: jnp.ndarray             # [L] bool: bounds moved since the
    #   leaf's own best was searched (needs an own re-search before it
    #   may speculate children again)


class _SimState(NamedTuple):
    """Tiny state for the serial leaf-wise ORDER simulation: which leaves
    get split this wave, in what order. Children enter the queue with their
    pre-searched (and depth-masked) gains, so no histogram data is touched
    — the heavy array updates happen vectorized afterwards."""
    gain: jnp.ndarray              # [L] f32 working copy of best gains
    ready: jnp.ndarray             # [L] bool working copy
    n_leaves: jnp.ndarray          # i32
    n_applied: jnp.ndarray         # i32
    app_leaf: jnp.ndarray          # [K] i32 parent leaf of applied split j
    mono_done: jnp.ndarray         # bool: a monotone-subtree split already
    #   landed this wave (intermediate-method serialization)


# rows a partition of a categorical table's root histogram (at 100M
# rows, 24 partitions). Partitions and exact counts are on for
# categorical tables only, so that a numeric table's kernels keep their
# text: a temporary fork (ROADMAP C17)
ROOT_PART_ROWS = 1 << 22


def wave_route(X_t: jnp.ndarray, cfg: GrowConfig) -> str:
    """How grow_tree_wave moves rows between leaves and builds its wave
    histograms, from what the table shows (the grower and the trainer's
    `wave_decide_trees` counter both ask here):

      "mega"      the wave megakernel (<= 32 dense storage columns, no
                  EFB, not feature-parallel, col-wise histograms):
                  relabel, membership and histogram in one
      "decide"    past it, unbundled: wave_decide_pallas decides each
                  row in the kernel, then the slots kernel
      "matrix"    EFB: the go-left bits of every row under every entry as
                  a [128, N] XLA matrix (bundle unpack), then
                  wave_apply_pallas
      "portable"  no Pallas (CPU meshes, >8-bit bins): XLA select chains

    A categorical table takes the route a numeric table of its width
    takes: its kernels test each entry's bitset (wave_cat_words).
    """
    from .histogram import _use_pallas
    if not _use_pallas(X_t, cfg.num_bins_padded):
        return "portable"
    if cfg.bundled:
        return "matrix"
    # hist_impl="rowwise" (config pin or autotune) takes the two-pass path
    # so its waves actually run the row-wise multi-value kernel — the
    # megakernel's histogram is col-wise only
    if (X_t.shape[0] <= 32 and not cfg.feature_parallel
            and cfg.hist_impl not in ("rowwise", "rowwise_packed")):
        return "mega"
    return "decide"


def grow_tree_wave(
    X_t: jnp.ndarray,            # [F, N] binned, feature-major
    grad: jnp.ndarray,           # [N] f32
    hess: jnp.ndarray,           # [N] f32
    in_bag: jnp.ndarray,         # [N] f32
    meta: FeatureMeta,
    cfg: GrowConfig,
    feature_mask: Optional[jnp.ndarray] = None,
    dist: Optional[object] = None,
    rng_seed: Optional[jnp.ndarray] = None,
    cegb_used: Optional[jnp.ndarray] = None,   # [F] bool: features already
    #   used by ANY split of the model (coupled-penalty state)
) -> tuple[DeviceTree, jnp.ndarray]:
    """Wave-pipelined exact leaf-wise growth; contract of grow.py:grow_tree."""
    # with EFB, X_t holds BUNDLE columns; F is the ORIGINAL feature count
    # (search/meta space), X_t.shape[0] the storage columns
    N = X_t.shape[1]
    F = int(meta.num_bins.shape[0])
    L = cfg.num_leaves
    M = max(L - 1, 1)
    B = cfg.num_bins_padded
    W = cfg.cat_words
    hp = cfg.hp
    max_depth = cfg.max_depth if cfg.max_depth > 0 else 10**9
    quant = cfg.use_quantized_grad

    # how a wave moves rows between leaves and builds its histograms
    # (wave_route): the wave megakernel; past it (use_apply) the
    # F-gridded slots kernel after wave_decide_pallas or, on EFB
    # storage, after wave_apply_pallas over a [128, N] decision matrix;
    # XLA select chains where Pallas does not run
    route = wave_route(X_t, cfg)
    # a categorical search reads counts (cat_smooth, min_data_per_group):
    # on such a table the children's counts are counted, not synthesized
    exact_counts = cfg.has_categorical
    use_mega = route == "mega"
    use_apply = route in ("decide", "matrix")
    if use_mega:
        # the megakernel's [HB*C*K, 32*LO] f32 output block lives in VMEM
        # for the whole grid; bound K so it stays within scoped VMEM.
        # The kernel pads the bin axis to the lane-friendly width, so the
        # budget must use that padded size, not cfg.num_bins_padded.
        from .histogram_pallas import _compute_dims
        B_lane = _compute_dims(B)[0]
        C_stat = 2          # (grad, hess) in both float and quantized mode
        # a categorical table's kernels cover its own columns (below);
        # a numeric table's the 32 rows of the padded table, as before
        F_k = X_t.shape[0] if cfg.has_categorical else 32
        kcap = 3_400_000 // (C_stat * F_k * B_lane * 4)
        kcap = max(1 << (kcap.bit_length() - 1), 1) if kcap >= 1 else 1
        buckets = _wave_buckets(L, min(kcap, 128))
        # wide-bin megakernel waves run the hi/lo one-hot decomposition
        # (histogram_pallas._compute_dims wide_lo, docs/PERF.md) unless
        # config/autotune pinned the legacy split. VMEM budget is
        # unchanged: HB*LO = B_lane for either choice, so kcap holds.
        mega_wide_lo = 64 if (B_lane > 128 and cfg.hist_impl
                              in ("auto", "tiered_hilo")) else 128
    else:
        buckets = _wave_buckets(L)
        mega_wide_lo = 128
    KMAX = buckets[-1]

    # feature-parallel holds the FULL data on every shard: row-statistic
    # reductions are local (a psum would overcount n_shards-fold)
    _row_local = dist is None or cfg.feature_parallel

    def psum(x):
        return x if _row_local else dist.psum(x)

    def pmax(x):
        return x if _row_local else dist.pmax(x)

    g = grad.astype(jnp.float32) * in_bag
    h = hess.astype(jnp.float32) * in_bag
    # counts are IN-BAG ROW COUNTS (0/1), not the in_bag multiplier: GOSS
    # amplification rides only on the gradients/hessians in the reference
    # (goss.hpp — bag indices are plain row sets), and 0/1 values stay
    # exact in the bf16 histogram contraction
    cnt_row = (in_bag > 0).astype(jnp.float32)

    # Histograms carry (grad, hess) ONLY — the reference's own entry
    # layout (bin.h:40: kHistEntrySize = 2 doubles). Per-bin counts are
    # synthesized at search time from hessians with the parent
    # count/hessian ratio, exactly the reference's cnt_factor behavior in
    # BOTH its float path (FindBestThresholdSequentially,
    # feature_histogram.hpp:529,844: RoundInt(hess * cnt_factor)) and its
    # int path (FindBestThresholdSequentiallyInt, :1077-1324). Dropping
    # the third exact-count channel cuts the MXU contraction cost and the
    # histogram caches by a third; root counts stay exact (computed from
    # in_bag directly) and leaf_count metadata descends via split records.
    if quant:
        # GradientDiscretizer::DiscretizeGradients semantics
        # (gradient_discretizer.cpp:72-162): per-tree scales synced by max
        # across shards, trunc-toward-zero stochastic rounding to int8,
        # exact int32 histogram accumulation.
        qb = cfg.num_grad_quant_bins
        max_g = pmax(jnp.max(jnp.abs(g)))
        max_h = pmax(jnp.max(h))
        g_scale = jnp.maximum(max_g / (qb // 2), 1e-30)
        h_scale = jnp.maximum(max_h / qb, 1e-30)
        if cfg.stochastic_rounding:
            seed = rng_seed if rng_seed is not None else jnp.int32(0)
            key = jax.random.PRNGKey(seed)
            kg, kh = jax.random.split(key)
            ug = jax.random.uniform(kg, (N,), jnp.float32)
            uh = jax.random.uniform(kh, (N,), jnp.float32)
        else:
            ug = uh = jnp.float32(0.5)
        g8 = jnp.clip(jnp.trunc(g / g_scale + jnp.sign(g) * ug),
                      -127, 127).astype(jnp.int8)
        h8 = jnp.clip(jnp.trunc(h / h_scale + uh), 0, 127).astype(jnp.int8)
        vals0 = jnp.stack([g8, h8], axis=0)              # [2, N] int8
        scale = jnp.stack([g_scale, h_scale])
    else:
        vals0 = jnp.stack([g, h], axis=0)                # [2, N] f32
        scale = None
    C = vals0.shape[0]

    def to_f32(histc):
        """Descale an int32 [C, F, B] histogram (no-op for f32 mode)."""
        if quant:
            return histc.astype(jnp.float32) * scale[:, None, None]
        return histc

    has_mono = meta.monotone is not None
    has_inter = meta.inter_sets is not None
    has_forced = meta.forced is not None
    has_cegb = (cfg.cegb_penalty_split > 0.0
                or meta.cegb_coupled is not None)
    if has_cegb and cegb_used is None:
        cegb_used = jnp.zeros((F,), bool)
    S = meta.inter_sets.shape[0] if has_inter else 1

    def sel_key(gain, is_forced, fid):
        """Wave selection/priority key: forced splits outrank everything
        and apply in BFS order (ForceSplits walks its queue before normal
        growth, serial_tree_learner.cpp:628); the stored split gain stays
        the real one."""
        if not has_forced:
            return gain
        return jnp.where(is_forced, 3e18 - fid.astype(jnp.float32) * 1e12,
                         gain)

    # ---- reduce-scatter feature ownership (tree_learner=data comm
    # scaling, data_parallel_tree_learner.cpp:72-122 PrepareBufferPos +
    # :286 ReduceScatter): each shard owns a feature slice of the summed
    # wave histograms, searches only its features, and the per-leaf best
    # splits are merged by an allgather of the tiny split records
    # (SyncUpGlobalBestSplit, parallel_tree_learner.h:210). Histogram
    # comm per wave drops from [K,C,F,B] allreduce-everywhere to a
    # reduce-scatter (1/n received) + O(K) record gather.
    # voting-parallel (PV-Tree, voting_parallel_tree_learner.cpp): shards
    # keep LOCAL histograms; per wave each shard votes its top-k features
    # by local gain, and only the 2k winning features' histogram columns
    # are psum-aggregated for the (exact-on-voted-features) split search.
    vo = (dist is not None and cfg.n_shards > 1 and cfg.voting_top_k > 0
          and not cfg.bundled)
    if vo and (has_forced or cfg.has_categorical or cfg.extra_trees
               or (has_mono and (cfg.monotone_method != "basic"
                                 or cfg.monotone_penalty > 0.0))):
        raise NotImplementedError(
            "tree_learner=voting does not support forced splits, "
            "categorical features, extra_trees, monotone_penalty or "
            "monotone_constraints_method=intermediate yet")
    # feature-parallel (feature_parallel_tree_learner.cpp:23-84): every
    # shard holds ALL rows, features partition across shards — histograms
    # are built directly on the local feature slice with NO histogram
    # collective at all; only the split records merge (the fo machinery's
    # allgather). fo (data-parallel reduce-scatter ownership) and fp are
    # mutually exclusive.
    fp = (dist is not None and cfg.n_shards > 1 and cfg.feature_parallel
          and not cfg.bundled and not vo)
    # parallel_hist_mode selects only the COLLECTIVE, never the search:
    # ownership (slice search + record merge) stays on in every mode, so
    # the grown trees are bit-identical across modes by construction —
    # under `allreduce` the full wave histogram is psum'd to every rank
    # and each rank slices out its own features locally (the autotune
    # probe's baseline wire profile; docs/PERF.md §Communication).
    # Exact-gain ties make the distinction observable otherwise: the
    # full-scan argmax is direction-major while the ownership merge is
    # feature-major, so a full search under allreduce could flip winners.
    fo = (dist is not None and cfg.n_shards > 1 and not cfg.bundled
          and not vo and not fp)
    # explicit reduce_scatter additionally syncs the per-wave best-split
    # records broadcast-free: order-encoded pmax keys + one masked psum
    # (parallel/packed.py) instead of the record all_gather.
    use_pmax_sync = fo and cfg.parallel_hist_mode == "reduce_scatter"
    # int32-packed-int16 collective payloads under quantized gradients
    # (bin.h:49-82 reducers): exact while the static carry bound holds,
    # halving ICI bytes for every histogram exchange in this tree.
    from ..parallel.packed import pack_gh, pack_safe, unpack_gh
    pack_ici = (quant and dist is not None and not cfg.feature_parallel
                and pack_safe(N * cfg.n_shards, cfg.num_grad_quant_bins))

    def exchange_hist(histc, collective, caxis):
        """Run `collective` over an int32/f32 histogram whose (grad,
        hess) channel pair lives on `caxis`, packing the pair into one
        int32 lane when safe (quantized mode only)."""
        if pack_ici:
            return unpack_gh(collective(pack_gh(histc, caxis)), caxis)
        return collective(histc)

    nsh = cfg.n_shards
    if fo or fp:
        from ..utils import round_up
        Fh_pad = round_up(F, nsh)
        Fs = Fh_pad // nsh
        foff = dist.axis_index() * Fs

        def _slice_f(a, ax, fill=0):
            if a is None:
                return None
            pads = [(0, 0)] * a.ndim
            pads[ax] = (0, Fh_pad - F)
            ap = jnp.pad(a, pads, constant_values=fill)
            return jax.lax.dynamic_slice_in_dim(ap, foff, Fs, ax)

        # padded features get num_bins=0: every bin invalid -> -inf gains
        meta_sh = meta._replace(
            num_bins=_slice_f(meta.num_bins, 0),
            missing_type=_slice_f(meta.missing_type, 0),
            default_bin=_slice_f(meta.default_bin, 0),
            is_categorical=_slice_f(meta.is_categorical, 0),
            monotone=_slice_f(meta.monotone, 0),
            inter_sets=(_slice_f(meta.inter_sets, 1)
                        if has_inter else None),
            cegb_coupled=_slice_f(meta.cegb_coupled, 0),
        )
        fmask_sh = (_slice_f(feature_mask, 0)
                    if feature_mask is not None else None)
    else:
        meta_sh, fmask_sh = meta, feature_mask

    def sets_to_fmask(sets_row, meta_u, fmask_u):
        """[S] bool active-constraint sets -> allowed features, combined
        with the global column-sampling mask (ColSampler with interaction
        constraints, col_sampler.hpp:208)."""
        m = jnp.any(meta_u.inter_sets & sets_row[:, None], axis=0)
        return m if fmask_u is None else m & fmask_u

    def make_search(meta_use, fmask_use, foffset=0):
      def search(hist2, sum_g, sum_h, count, out, bmin, bmax, sets_row,
                 forced_id=None, used_f=None, fmask_dyn=None,
                 rand_dyn=None, mono_pf=None):
        if cfg.bundled:
            # EFB: re-slice the bundle histogram per ORIGINAL feature
            # (Dataset::ConstructHistograms offsets) and reconstruct each
            # feature's default bin as parent - sum(others)
            # (Dataset::FixHistogram, dataset.h:778)
            flat = hist2.reshape(C, -1)
            hist2 = jnp.take(flat, meta.bundle_expand, axis=1,
                             mode="fill", fill_value=0).reshape(C, F, B)
            hist2 = to_f32(hist2)
            parent = jnp.stack(
                [sum_g, sum_h, count.astype(jnp.float32)][:C])
            miss = parent[:, None] - jnp.sum(hist2, axis=-1)    # [C, F]
            hist2 = hist2 + meta.bundle_mfb[None] * miss[:, :, None]
        else:
            hist2 = to_f32(hist2)
        hist = synth_count_channel(hist2, count, sum_h)   # [3, F, B]
        fmask = (sets_to_fmask(sets_row, meta_use, fmask_use)
                 if has_inter else fmask_use)
        if fmask_dyn is not None:
            F_use = int(meta_use.num_bins.shape[0])
            fd = fmask_dyn
            if fd.shape[0] != F_use:      # sharded search: own slice
                fd = jax.lax.dynamic_slice_in_dim(
                    jnp.pad(fd, (0, F_use * nsh - fd.shape[0])),
                    foffset, F_use, 0)
            fmask = fd if fmask is None else (fmask & fd)
        rand_b = None
        if rand_dyn is not None:
            F_use = int(meta_use.num_bins.shape[0])
            rand_b = rand_dyn
            if rand_b.shape[0] != F_use:  # sharded search: own slice
                rand_b = jax.lax.dynamic_slice_in_dim(
                    jnp.pad(rand_b, (0, F_use * nsh - rand_b.shape[0])),
                    foffset, F_use, 0)
        pen = None
        if has_cegb and used_f is not None:
            # DeltaGain (cost_effective_gradient_boosting.hpp:81):
            # tradeoff * (penalty_split * leaf_count + coupled on first
            # feature use). Documented divergence from the reference:
            # UpdateLeafBestSplits (:96-117) re-searches OTHER leaves'
            # cached splits when a feature first becomes used (their
            # coupled penalty drops); here already-speculated leaves keep
            # their penalized cached gains until their next natural
            # re-search — a bounded approximation (at most one wave of
            # staleness per feature first-use)
            F_use = int(meta_use.num_bins.shape[0])
            u = used_f
            if u.shape[0] != F_use:       # sharded search: own slice
                u = jax.lax.dynamic_slice_in_dim(
                    jnp.pad(u, (0, F_use * nsh - u.shape[0])),
                    foffset, F_use, 0)
            pen = jnp.full((F_use,),
                           cfg.cegb_tradeoff * cfg.cegb_penalty_split
                           * count, jnp.float32)
            if meta_use.cegb_coupled is not None:
                pen = pen + cfg.cegb_tradeoff * meta_use.cegb_coupled \
                    * (1.0 - u.astype(jnp.float32))
        fres = None
        if has_forced and forced_id is not None:
            # one shared gain map yields both the normal best and the
            # forced (feature, threshold) cell
            from .split import find_best_split_and_forced
            fid_c = jnp.clip(forced_id, 0, meta.forced.shape[1] - 1)
            ff = meta.forced[0, fid_c] - foffset
            fb = meta.forced[1, fid_c]
            num, fres = find_best_split_and_forced(
                hist, sum_g, sum_h, count, out, meta_use, hp, fmask,
                bmin if has_mono else None,
                bmax if has_mono else None, ff, fb, cegb_pen=pen,
                rand_bins=rand_b, mono_pen_factor=mono_pf)
        else:
            num = find_best_split(hist, sum_g, sum_h, count, out,
                                  meta_use, hp, fmask,
                                  leaf_min=bmin if has_mono else None,
                                  leaf_max=bmax if has_mono else None,
                                  cegb_pen=pen, rand_bins=rand_b,
                                  mono_pen_factor=mono_pf)
        nob = jnp.zeros((W,), jnp.uint32)
        if not cfg.has_categorical:
            merged, use_cat, bits = num, jnp.zeros((), bool), nob
        else:
            with jax.named_scope("lgbm_cat_split"):
                catres, bitset = find_best_split_categorical(
                    hist, sum_g, sum_h, count, out, meta_use, hp, cfg.cat,
                    fmask,
                    leaf_min=bmin if has_mono else None,
                    leaf_max=bmax if has_mono else None,
                    cegb_pen=pen)
            use_cat = catres.gain > num.gain
            merged = SplitResult(*[
                jnp.where(use_cat, cv, nv) for cv, nv in zip(catres, num)])
            bits = jnp.where(use_cat, bitset, nob)
        if fres is None:
            return merged, use_cat, bits, jnp.zeros((), bool)
        # forced-split override: fixed (feature, threshold) from the
        # forced table. In sharded search the forced feature may live on
        # another shard (local id out of range -> -inf; the owner wins
        # at merge time).
        use_f = (forced_id >= 0) & jnp.isfinite(fres.gain)
        merged = SplitResult(*[
            jnp.where(use_f, fv, mv) for fv, mv in zip(fres, merged)])
        return (merged, use_cat & ~use_f, jnp.where(use_f, nob, bits),
                use_f)
      return search

    # every call of a search, vmapped or not, traces under one scope name
    in_search_scope = jax.named_scope("train/split_search")
    search = in_search_scope(make_search(meta, feature_mask))
    search_sh = in_search_scope(make_search(meta_sh, fmask_sh, foff)) \
        if (fo or fp) else search

    if fp:
        # each shard histograms ONLY its feature slice (over all rows)
        X_pad_fp = jnp.pad(X_t, ((0, Fh_pad - F), (0, 0)))
        X_hist = jax.lax.dynamic_slice_in_dim(X_pad_fp, foff, Fs, 0)
    else:
        X_hist = X_t

    # per-node column sampling (ColSampler::GetByNode, col_sampler.hpp:208)
    bynode = cfg.feature_fraction_bynode < 1.0

    def node_masks(key, n):
        """[n, F] bool: exactly max(1, fraction*F) features kept per node;
        the key derives from replicated values so all shards agree."""
        k_keep = max(1, int(F * cfg.feature_fraction_bynode))
        u = jax.random.uniform(key, (n, F))
        kth = -jax.lax.top_k(-u, k_keep)[0][:, -1:]
        return u <= kth

    if bynode:
        _bn_seed = rng_seed if rng_seed is not None else jnp.int32(0)
        _bn_base = jax.random.PRNGKey(_bn_seed + 0x5EED)

    # extra_trees: one random threshold per (node, feature), keyed by
    # replicated values so every shard draws identically
    xt = cfg.extra_trees
    if xt:
        _xt_seed = rng_seed if rng_seed is not None else jnp.int32(0)
        _xt_base = jax.random.PRNGKey(_xt_seed * 31 + cfg.extra_seed)

    def xt_bins(key, n):
        """[n, F] uniform thresholds in [0, max(num_bin-2, 1))."""
        hi = jnp.maximum(meta.num_bins - 2, 1)
        u = jax.random.uniform(key, (n, F))
        return jnp.minimum((u * hi[None, :]).astype(jnp.int32), hi - 1)

    @in_search_scope
    def search_voted(hist2, sum_g, sum_h, count, out, bmin, bmax,
                     sets_row, mv_nb, mv_mt, mv_db, mv_mono, mv_inter,
                     mv_fmask):
        """Split search over the AGGREGATED voted feature columns (exact
        for voted features: global histograms + global parent stats).
        Meta arrays arrive gathered per voted feature (dynamic)."""
        hist = synth_count_channel(to_f32(hist2), count, sum_h)
        mv = FeatureMeta(
            num_bins=mv_nb, missing_type=mv_mt, default_bin=mv_db,
            is_categorical=jnp.zeros_like(mv_nb, bool),
            monotone=mv_mono, inter_sets=mv_inter)
        if has_inter:
            fmask = jnp.any(mv_inter & sets_row[:, None], axis=0)
            if mv_fmask is not None:
                fmask = fmask & mv_fmask
        else:
            fmask = mv_fmask
        res = find_best_split(hist, sum_g, sum_h, count, out, mv, hp,
                              fmask,
                              leaf_min=bmin if has_mono else None,
                              leaf_max=bmax if has_mono else None)
        return (res, jnp.zeros((), bool), jnp.zeros((W,), jnp.uint32),
                jnp.zeros((), bool))

    def child_sets(bs, psets):
        """Constraint sets still satisfiable in the children: the parent's
        sets that contain the split feature (both children alike)."""
        if not has_inter:
            return psets
        contains = jnp.take(meta.inter_sets.T, bs.feature, axis=0)  # [K, S]
        return psets & contains

    mono_inter = cfg.monotone_method == "intermediate"
    use_mpen = has_mono and cfg.monotone_penalty > 0.0

    def mpen_factor(depth):
        """monotone_penalty gain multiplier by leaf depth
        (ComputeMonotoneSplitGainPenalty, monotone_constraints.hpp:358;
        kEpsilon = 1e-15)."""
        pen = cfg.monotone_penalty
        eps = 1e-15
        d = depth.astype(jnp.float32)
        if pen <= 1.0:
            f = 1.0 - pen / jnp.exp2(d) + eps
        else:
            f = 1.0 - jnp.exp2(pen - 1.0 - d) + eps
        return jnp.where(pen >= d + 1.0, eps, f)

    def child_bounds(bs, pmin, pmax):
        """Children's monotone output bounds after a split.

        basic (BasicLeafConstraints::Update, monotone_constraints.hpp:330):
        children separate at the MIDPOINT of the (clamped) outputs.
        intermediate (IntermediateLeafConstraints::
        UpdateConstraintsWithOutputs, :548): each child is bounded by the
        SIBLING's actual output — less conservative, higher gains. The
        intermediate bounds are refreshed against current subtree output
        extrema every wave (refresh_monotone_bounds below), which is the
        batched fixpoint of the reference's leaves_to_update repair
        walks (GoUpToFindLeavesToUpdate, :625)."""
        if not has_mono:
            z = jnp.zeros_like(bs.gain)
            return z, z, z, z
        mono_f = meta.monotone[bs.feature]
        if mono_inter:
            lcap, rcap = bs.right_output, bs.left_output
        else:
            lcap = rcap = 0.5 * (bs.left_output + bs.right_output)
        lmax = jnp.where(mono_f > 0, jnp.minimum(pmax, lcap), pmax)
        rmin = jnp.where(mono_f > 0, jnp.maximum(pmin, rcap), pmin)
        lmin = jnp.where(mono_f < 0, jnp.maximum(pmin, lcap), pmin)
        rmax = jnp.where(mono_f < 0, jnp.minimum(pmax, rcap), pmax)
        return lmin, lmax, rmin, rmax

    # ---- root
    # feature-parallel builds the root on its feature slice only (the
    # whole point of the learner: 1/n of the histogram work per shard)
    with jax.named_scope("train/root_histogram"):
        X_root = X_hist if fp else X_t
        if cfg.has_categorical and N > ROOT_PART_ROWS:
            # in row partitions, summed apart and then together, so the
            # root's float32 bins do not drift (histogram_pallas
            # wave_parts); a categorical search sorts the bins they
            # leave in every larger child. The partitions are a vmapped
            # axis: one call, whose kernels take it as a grid axis
            from ..utils import round_up
            P = -(-N // ROOT_PART_ROWS)
            rows = round_up(-(-N // P), 1024)

            def parts(a):
                a = jnp.pad(a, ((0, 0), (0, P * rows - N)))
                return a.reshape(a.shape[0], P, rows).transpose(1, 0, 2)

            hist_root_local = jnp.sum(jax.vmap(
                lambda x, v: build_histogram(
                    x, v, B, cfg.rows_per_chunk, tiers=cfg.hist_tiers,
                    impl=cfg.hist_impl))(parts(X_root), parts(vals0)),
                axis=0)
        else:
            hist_root_local = build_histogram(X_root, vals0, B,
                                              cfg.rows_per_chunk,
                                              tiers=cfg.hist_tiers,
                                              impl=cfg.hist_impl)
        hist_root = exchange_hist(hist_root_local, psum, 0)
    # the exchanged root histogram is whole on every rank here (the
    # ownership modes reduce-scatter the WAVES, not the root; a
    # feature-parallel shard holds all rows, so its own first column, a
    # padded all-zero-bin one included, holds every row), and with EFB
    # its columns are still the raw bundles
    root_g, root_h, root_c, root_out = root_totals(
        hist_root, cnt_row, hp, psum, scale)
    root_fid = jnp.asarray(0 if has_forced else -1, jnp.int32)
    used0 = (cegb_used if has_cegb else jnp.zeros((F,), bool))
    root_kwargs = dict(
        forced_id=root_fid, used_f=used0,
        fmask_dyn=(node_masks(jax.random.fold_in(_bn_base, 0), 1)[0]
                   if bynode else None),
        rand_dyn=(xt_bins(jax.random.fold_in(_xt_base, 0), 1)[0]
                  if xt else None),
        mono_pf=(mpen_factor(jnp.zeros((), jnp.int32)) if use_mpen
                 else None))
    root_search_fn = search_sh if fp else search
    root_split, root_is_cat, root_bitset, root_forced = root_search_fn(
        hist_root, root_g, root_h, root_c, root_out,
        jnp.float32(-jnp.inf), jnp.float32(jnp.inf),
        jnp.ones((S,), bool), **root_kwargs)
    if fp:
        # merge the per-shard root records (SyncUpGlobalBestSplit)
        root_split = root_split._replace(feature=root_split.feature + foff)
        rec = (tuple(root_split), root_is_cat, root_bitset, root_forced)
        allr = jax.tree.map(
            lambda a: dist.all_gather(a[None], axis=0, tiled=False), rec)
        rkey = allr[0][0][:, 0]
        if has_forced:
            rkey = jnp.where(allr[3][:, 0], 2e18, rkey)
        rpick = jnp.argmax(rkey)
        root_split = SplitResult(*[a[rpick, 0] for a in allr[0]])
        root_is_cat = allr[1][rpick, 0]
        root_bitset = allr[2][rpick, 0]
        root_forced = allr[3][rpick, 0]
    root_split = root_split._replace(
        gain=jnp.where(max_depth >= 1, root_split.gain, NEG_INF))
    root_forced &= max_depth >= 1
    if fp:
        # the cache IS the local slice already
        pads = [(0, 0)] * hist_root.ndim
        pads[1] = (0, Fs - hist_root.shape[1])
        hist_cache0 = jnp.pad(hist_root, pads)
    elif fo:
        # the per-shard caches hold this shard's feature slice only
        pads = [(0, 0)] * hist_root.ndim
        pads[1] = (0, Fh_pad - hist_root.shape[1])
        hist_cache0 = jax.lax.dynamic_slice_in_dim(
            jnp.pad(hist_root, pads), foff, Fs, 1)
    elif vo:
        # voting: caches hold LOCAL histograms (subtraction stays local;
        # only voted columns ever cross the wire)
        hist_cache0 = hist_root_local
    else:
        hist_cache0 = hist_root
    # caches live FLAT [L, C*F*B]: a 2D state array keeps XLA from picking
    # a leaf-minor layout for the per-wave gather/scatter one-hot matmuls
    # (profiled at ~29 ms/tree of pure relayout copies with 4D caches)
    hshape = hist_cache0.shape
    hist_cache0 = hist_cache0.reshape(-1)

    tree = _root_tree(L, W, root_h, root_c)
    if use_mega:
        # the root's contraction took every row
        tree = tree._replace(wave_counts=jnp.stack([
            jnp.uint32(0), jnp.uint32(0), psum(jnp.uint32(N))]))
    empty = _empty_split_cache(L)
    state = _WaveState(
        tree=tree,
        leaf_of_row=jnp.zeros((N,), jnp.int32),
        leaf_parent_node=jnp.full((L,), -1, jnp.int32),
        leaf_is_left=jnp.zeros((L,), bool),
        leaf_depth=jnp.zeros((L,), jnp.int32),
        leaf_output=jnp.zeros((L,), jnp.float32).at[0].set(root_out),
        leaf_sum_g=jnp.zeros((L,), jnp.float32).at[0].set(root_g),
        leaf_sum_h=jnp.zeros((L,), jnp.float32).at[0].set(root_h),
        hist_cache=jnp.zeros((L,) + hist_cache0.shape,
                             hist_cache0.dtype).at[0].set(hist_cache0),
        small_hist=jnp.zeros((L,) + hist_cache0.shape, hist_cache0.dtype),
        small_is_left=jnp.zeros((L,), bool),
        ready=jnp.zeros((L,), bool),
        leaf_min=jnp.full((L,), -jnp.inf, jnp.float32),
        leaf_max=jnp.full((L,), jnp.inf, jnp.float32),
        leaf_sets=jnp.ones((L, S), bool),
        best=_set_cache(empty, 0, root_split, True),
        best_is_cat=jnp.zeros((L,), bool).at[0].set(root_is_cat),
        best_bitset=jnp.zeros((L, W), jnp.uint32).at[0].set(root_bitset),
        bestl=empty, bestr=empty,
        catl=jnp.zeros((L,), bool), catr=jnp.zeros((L,), bool),
        bitsl=jnp.zeros((L, W), jnp.uint32),
        bitsr=jnp.zeros((L, W), jnp.uint32),
        leaf_forced=jnp.full((L,), -1, jnp.int32).at[0].set(root_fid),
        best_forced=jnp.zeros((L,), bool).at[0].set(root_forced),
        feat_used=used0,
        fidl=jnp.full((L,), -1, jnp.int32),
        fidr=jnp.full((L,), -1, jnp.int32),
        bfl=jnp.zeros((L,), bool),
        bfr=jnp.zeros((L,), bool),
        under=jnp.zeros((L, M), jnp.int8),
        stale=jnp.zeros((L,), bool),
    )

    def dec_go_left(tbl_leaf, feat, thr, dl, iscat, bits):
        """[K, N] go-left decision of EVERY row under each table entry's
        split, vectorized over entries (inactive entries produce garbage
        bits that the membership kernel never reads). Bundle unpacking
        follows FastFeatureBundling's inverse (dataset.cpp:251);
        categorical tests the bin bitset."""
        featc = jnp.clip(feat, 0, F - 1)
        if cfg.bundled:
            colK = jnp.asarray(cfg.bundle_col, jnp.int32)[featc]
            src = jnp.take(X_t, colK, axis=0).astype(jnp.int32) & 0xFF
            off = jnp.asarray(cfg.bundle_off, jnp.int32)[featc][:, None]
            nbf = jnp.asarray(cfg.bundle_nb, jnp.int32)[featc][:, None]
            dbf = jnp.asarray(cfg.bundle_db, jnp.int32)[featc][:, None]
            rb = src - off
            inr = (rb >= 0) & (rb < nbf - 1)
            unp = jnp.where(inr, rb + (rb >= dbf), dbf)
            binv = jnp.where(off < 0, src, unp)
        else:
            binv = jnp.take(X_t, featc, axis=0).astype(jnp.int32) & 0xFF
        mt = meta.missing_type[featc][:, None]
        db = meta.default_bin[featc][:, None]
        nb = meta.num_bins[featc][:, None]
        miss = ((mt == MISSING_ZERO) & (binv == db)) | \
               ((mt == MISSING_NAN) & (binv == nb - 1))
        gl = jnp.where(miss, dl[:, None].astype(bool),
                       binv <= thr[:, None])
        if cfg.has_categorical:
            widx = jnp.clip(binv >> 5, 0, W - 1)
            wsel = jnp.zeros(binv.shape, jnp.uint32)
            for w in range(W):
                wsel = jnp.where(widx == w, bits[:, w:w + 1], wsel)
            gl_cat = ((wsel >> (binv & 31).astype(jnp.uint32)) & 1) == 1
            gl = jnp.where(iscat[:, None], gl_cat, gl)
        return gl

    def table_go_left(leaf_of_row, tbl_leaf, sp_feat, sp_thr, sp_dleft,
                      sp_iscat, sp_bits):
        """Evaluate each in-table row against its leaf's split; pure
        elementwise. Returns (slot [N] i32 clamped, in_table, go_left).
        `tbl_leaf` [K] holds the leaf id per slot, -1 for inactive slots.

        EVERYTHING here is compare-select chains over the wave table and
        the features — [N]-sized gathers from small tables lower to
        ~2 GB/s loops on this target (profiled at ~4ms per gather per
        wave), while the fused select chains run at VPU speed."""
        slot = jnp.full((N,), -1, jnp.int32)
        feat = jnp.zeros((N,), jnp.int32)
        thr = jnp.zeros((N,), jnp.int32)
        dleft = jnp.zeros((N,), bool)
        iscat = jnp.zeros((N,), bool)
        for j in range(tbl_leaf.shape[0]):
            m = leaf_of_row == tbl_leaf[j]
            slot = jnp.where(m, j, slot)
            feat = jnp.where(m, sp_feat[j], feat)
            thr = jnp.where(m, sp_thr[j], thr)
            dleft = jnp.where(m, sp_dleft[j], dleft)
            iscat = iscat | (m & sp_iscat[j])
        in_tbl = slot >= 0

        col = jnp.zeros((N,), jnp.int32)
        mt = jnp.zeros((N,), jnp.int32)
        db = jnp.zeros((N,), jnp.int32)
        nb = jnp.zeros((N,), jnp.int32)
        for f in range(F):
            if cfg.bundled:
                src = X_t[cfg.bundle_col[f]].astype(jnp.int32)
                off = cfg.bundle_off[f]
                if off < 0:
                    binv = src               # raw singleton column
                else:
                    # unpack the bundle slot back to the feature's bins
                    # (FastFeatureBundling inverse, dataset.cpp:251)
                    nbf, dbf = cfg.bundle_nb[f], cfg.bundle_db[f]
                    rb = src - off
                    inr = (rb >= 0) & (rb < nbf - 1)
                    binv = jnp.where(inr, rb + (rb >= dbf), dbf)
            else:
                binv = X_t[f].astype(jnp.int32)
            fm = feat == f
            col = jnp.where(fm, binv, col)
            mt = jnp.where(fm, meta.missing_type[f], mt)
            db = jnp.where(fm, meta.default_bin[f], db)
            nb = jnp.where(fm, meta.num_bins[f], nb)

        is_missing = ((mt == MISSING_ZERO) & (col == db)) | \
                     ((mt == MISSING_NAN) & (col == nb - 1))
        gl_num = jnp.where(is_missing, dleft, col <= thr)
        if cfg.has_categorical:
            widx = jnp.clip(col >> 5, 0, W - 1)
            wsel = jnp.zeros((N,), jnp.uint32)
            for j in range(tbl_leaf.shape[0]):
                m = slot == j
                for w in range(W):
                    wsel = jnp.where(m & (widx == w), sp_bits[j, w], wsel)
            gl_cat = ((wsel >> (col & 31).astype(jnp.uint32)) & 1) == 1
            go_left = jnp.where(iscat, gl_cat, gl_num)
        else:
            go_left = gl_num
        return jnp.maximum(slot, 0), in_tbl, go_left

    def make_hist_branch(K):
        def branch(slot_small):
            hist = build_histogram_slots(X_hist, vals0, slot_small, K, B,
                                         cfg.rows_per_chunk,
                                         tiers=cfg.hist_tiers,
                                         impl=cfg.hist_impl)
            if K < KMAX:
                hist = jnp.pad(hist, ((0, KMAX - K), (0, 0), (0, 0), (0, 0)))
            return hist
        return branch

    hist_branches = [make_hist_branch(K) for K in buckets]
    bucket_bounds = jnp.asarray(buckets, jnp.int32)

    # ---- fused wave megakernel (TPU): one pass over the rows performs
    # split application (relabel), candidate smaller-child membership and
    # the slot histogram — replacing three separate [N]-sized XLA passes
    # whose intermediates each round-trip HBM (histogram_pallas.py
    # _wave_kernel). Falls back to the portable path for CPU meshes,
    # bundled (EFB) storage, categorical splits, or wide feature counts.
    if use_mega:
        from .histogram_pallas import (wave_pass_pallas,
                                       wave_relabel_pallas, wave_parts,
                                       N_BLK)
        from ..utils import round_up
        F0 = X_t.shape[0]
        n_blk = N_BLK if N >= N_BLK else max(round_up(N, 256), 256)
        # a categorical table's wave pass sums row partitions apart
        Np = round_up(N, n_blk * (wave_parts(N) if cfg.has_categorical
                                  else 1))
        # pad/convert once per tree; every wave kernel reuses these
        X_mega = jnp.pad(X_t.astype(jnp.int8),
                         ((0, 32 - F0), (0, Np - N)))
        vals_mega = jnp.pad(vals0, ((0, 0), (0, Np - N)))
        hist_dtype = jnp.int32 if quant else jnp.float32
        from .histogram import pallas_interpret
        _interp_m = pallas_interpret()

        def make_mega_branch(K):
            def branch(args):
                lor, tbl16, *cat = args
                # a categorical table's kernel also counts each slot's
                # in-bag rows (the smaller children's exact counts)
                out = wave_pass_pallas(X_mega, vals_mega, lor, tbl16, K, B,
                                       interpret=_interp_m,
                                       wide_lo=mega_wide_lo,
                                       cat=cat[0] if cat else None,
                                       in_bag=cnt_row if cat else None,
                                       features=F0 if cat else 0)
                new_lor, hist = out[0], out[1][:, :, :F0, :]
                if K < KMAX:
                    hist = jnp.pad(
                        hist, ((0, KMAX - K), (0, 0), (0, 0), (0, 0)))
                # the rows its contraction took (wave_counts)
                n_rows = out[-1].astype(jnp.uint32)
                if not cat:
                    return new_lor, hist, n_rows
                cnt = jnp.pad(out[2], (0, KMAX - K)).astype(jnp.float32)
                return new_lor, hist, cnt, n_rows
            return branch

        def relabel_only_branch(args):
            # final wave of a tree: splits to apply, no candidates left —
            # skip the histogram contraction entirely
            lor, tbl16, *cat = args
            new_lor = wave_relabel_pallas(X_mega, vals_mega, lor, tbl16, B,
                                          interpret=_interp_m,
                                          cat=cat[0] if cat else None,
                                          features=F0 if cat else 0)
            hist = jnp.zeros((KMAX, C, F0, B), hist_dtype)
            none = jnp.zeros((), jnp.uint32)
            if not cat:
                return new_lor, hist, none
            return new_lor, hist, jnp.zeros((KMAX,), jnp.float32), none

        mega_branches = [relabel_only_branch] \
            + [make_mega_branch(K) for K in buckets]

    # ---- serial ORDER simulation: each step touches only [L]-sized gain/
    # ready arrays (~10 tiny ops), so the 254-step sequential chain costs
    # milliseconds; the heavy per-split state updates happen vectorized in
    # wave_step afterwards. gl/gr are the children's (depth-masked) gains.
    def make_sim(gl, gr, im=None):
        def blocked(s, p):
            if im is None:
                return jnp.bool_(False)
            return im[p] & s.mono_done

        def sim_step(s: _SimState) -> _SimState:
            p = jnp.argmax(s.gain).astype(jnp.int32)
            ok = (s.gain[p] > 0.0) & s.ready[p] & (s.n_leaves < L) \
                & (s.n_applied < KMAX) & ~blocked(s, p)
            r = s.n_leaves                                   # new leaf id
            gain = s.gain.at[p].set(jnp.where(ok, gl[p], s.gain[p]))
            gain = gain.at[jnp.where(ok, r, L)].set(gr[p], mode="drop")
            return _SimState(
                gain=gain,
                ready=s.ready.at[p].set(jnp.where(ok, False, s.ready[p])),
                n_leaves=s.n_leaves + ok.astype(jnp.int32),
                n_applied=s.n_applied + ok.astype(jnp.int32),
                app_leaf=s.app_leaf.at[s.n_applied].set(
                    jnp.where(ok, p, s.app_leaf[s.n_applied])),
                mono_done=s.mono_done | (ok & (im[p] if im is not None
                                               else False)),
            )

        def sim_cond(s: _SimState):
            p = jnp.argmax(s.gain)
            return (s.gain[p] > 0.0) & s.ready[p] & (s.n_leaves < L) \
                & (s.n_applied < KMAX) & ~blocked(s, p)

        return sim_cond, sim_step

    def table_go_left_bucketed(n_active, leaf_of_row, tbl, f, t, d, ic, bt):
        """table_go_left with the select-chain length bucketed to the
        actual wave size (active entries are a prefix): small waves must
        not pay the KMAX-length compare chain."""
        def mk(Kb):
            def br(args):
                lor, tbl_, f_, t_, d_, ic_, bt_ = args
                return table_go_left(lor, tbl_[:Kb], f_[:Kb], t_[:Kb],
                                     d_[:Kb], ic_[:Kb], bt_[:Kb])
            return br
        kidx = jnp.minimum(
            jnp.searchsorted(bucket_bounds, n_active).astype(jnp.int32),
            len(buckets) - 1)
        return jax.lax.switch(kidx, [mk(Kb) for Kb in buckets],
                              (leaf_of_row, tbl, f, t, d, ic, bt))

    def wave_step(st: _WaveState) -> _WaveState:
        j_iota = jnp.arange(KMAX, dtype=jnp.int32)

        if has_mono and mono_inter:
            # leaves under an existing monotone node (their applications
            # must serialize — see the batched branch below)
            node_act0 = jnp.arange(M) < st.tree.num_leaves - 1
            mono_n0 = jnp.where(
                node_act0,
                meta.monotone[st.tree.split_feature].astype(jnp.int32), 0)
            im_leaf = jnp.any((st.under != 0) & (mono_n0 != 0)[None, :],
                              axis=1)                         # [L]
        else:
            im_leaf = None

        # ---- ORDER: which ready leaves split this wave, in what order
        budget = L - st.tree.num_leaves
        if cfg.wave_exact:
            # strict leaf-wise: serial simulation that blocks when the
            # priority-queue head has no speculated child data yet
            # (sel_key lets pending forced splits outrank normal ones)
            sim_cond, sim_step = make_sim(
                sel_key(st.bestl.gain, st.bfl, st.fidl),
                sel_key(st.bestr.gain, st.bfr, st.fidr), im=im_leaf)
            sim = jax.lax.while_loop(sim_cond, sim_step, _SimState(
                gain=sel_key(st.best.gain, st.best_forced, st.leaf_forced),
                ready=st.ready,
                n_leaves=st.tree.num_leaves,
                n_applied=jnp.asarray(0, jnp.int32),
                app_leaf=jnp.full((KMAX,), -1, jnp.int32),
                mono_done=jnp.bool_(False)))
            napp = sim.n_applied
            app_leaf = sim.app_leaf
        else:
            # batched frontier: ready leaves with positive gain split in
            # gain order, trimmed to the leaf budget. The gain-slack guard
            # makes a high-gain not-yet-ready child block lesser splits
            # (approaching strict leaf-wise order as slack -> 1) — but at
            # least the top half of the ready set always applies, so a
            # dominant-gain chain cannot degenerate to one split per wave
            # (O(L) waves observed without this).
            keyed = sel_key(st.best.gain, st.best_forced, st.leaf_forced)
            ready_gain = jnp.where(st.ready, keyed, NEG_INF)
            rg, rl = jax.lax.top_k(ready_gain, KMAX)
            sel = (rg > 0.0) & (j_iota < budget)
            if cfg.wave_gain_slack > 0.0:
                # the slack guard exists to keep late budget for
                # higher-gain speculated children (strict leaf-wise would
                # split those first) — while the leaf budget is plentiful,
                # deferring a ready leaf only fragments waves: every split
                # with positive gain will fit anyway. Engage the guard
                # only under budget pressure.
                npos = jnp.sum(sel).astype(jnp.int32)
                guard = rg >= cfg.wave_gain_slack * jnp.max(keyed)
                if L < 64:
                    # small trees: order quality dominates and waves are
                    # cheap — keep the guard always on
                    pressure = jnp.bool_(True)
                else:
                    pressure = 2 * npos >= budget
                sel &= guard | (j_iota < (npos + 1) // 2) | ~pressure
            if has_mono and mono_inter:
                # intermediate bounds derive from SIBLING outputs, which
                # move as splits land: applying two leaves that share a
                # monotone ancestor in one wave would use stale bounds
                # (the reference applies sequentially and repairs
                # immediately). Serialize: at most ONE split per wave
                # among leaves under any monotone node.
                im_split = meta.monotone[st.best.feature] != 0  # [L]
                ser = im_leaf | im_split
                sel_mono = sel & ser[rl]
                first = (jnp.cumsum(sel_mono.astype(jnp.int32))
                         == 1) & sel_mono
                sel &= ~sel_mono | first
            napp = jnp.sum(sel).astype(jnp.int32)
            app_leaf = jnp.where(sel, rl.astype(jnp.int32), -1)
        appv = j_iota < napp                                 # [K] bool
        nl0 = st.tree.num_leaves
        p_j = jnp.maximum(app_leaf, 0)                       # [K] parents
        s_j = nl0 - 1 + j_iota                               # [K] node ids
        r_j = nl0 + j_iota                                   # [K] new leaves
        drop_p = jnp.where(appv, p_j, L)                     # OOB = dropped
        drop_r = jnp.where(appv, r_j, L)
        drop_s = jnp.where(appv, s_j, M)

        # ---- APPLY: write the selected splits into the tree and the
        # per-leaf state
        with jax.named_scope("train/apply"):
            t = st.tree
            bs2 = SplitResult(*[x[p_j] for x in st.best])
            iscat2 = st.best_is_cat[p_j]
            bits2 = st.best_bitset[p_j]

            def rec(arr, v):
                return arr.at[drop_s].set(v, mode="drop")

            t = t._replace(
                split_feature=rec(t.split_feature, bs2.feature),
                threshold_bin=rec(t.threshold_bin, bs2.threshold),
                default_left=rec(t.default_left, bs2.default_left),
                split_gain=rec(t.split_gain, bs2.gain),
                left_child=rec(t.left_child, ~p_j),
                right_child=rec(t.right_child, ~r_j),
                internal_value=rec(t.internal_value, st.leaf_output[p_j]),
                internal_weight=rec(t.internal_weight, st.leaf_sum_h[p_j]),
                internal_count=rec(t.internal_count, t.leaf_count[p_j]),
                split_parent_leaf=rec(t.split_parent_leaf, p_j),
                split_is_cat=rec(t.split_is_cat, iscat2),
                split_cat_bitset=t.split_cat_bitset.at[drop_s].set(
                    bits2, mode="drop"),
                num_leaves=nl0 + napp,
            )
            # rewire parent node child pointers (~p_j -> s_j). Sibling leaves
            # may be applied in the SAME wave (same parent node), so the
            # non-writing side must be dropped via out-of-range indices.
            prev = st.leaf_parent_node[p_j]
            fix = appv & (prev >= 0)
            was_left = st.leaf_is_left[p_j]
            t = t._replace(
                left_child=t.left_child.at[
                    jnp.where(fix & was_left, prev, M)].set(s_j, mode="drop"),
                right_child=t.right_child.at[
                    jnp.where(fix & ~was_left, prev, M)].set(s_j, mode="drop"))

            def upd2(arr, lv, rv, cast=None):
                if cast is not None:
                    lv, rv = lv.astype(cast), rv.astype(cast)
                arr = arr.at[drop_p].set(lv, mode="drop")
                return arr.at[drop_r].set(rv, mode="drop")

            t = t._replace(
                leaf_value=upd2(t.leaf_value, bs2.left_output,
                                bs2.right_output),
                leaf_weight=upd2(t.leaf_weight, bs2.left_sum_h,
                                 bs2.right_sum_h),
                leaf_count=upd2(t.leaf_count, bs2.left_count, bs2.right_count,
                                jnp.int32),
            )
            depth_child = st.leaf_depth[p_j] + 1

            # children own-histograms from the speculative pass + subtraction.
            # One-hot matmul gathers/scatters: XLA's dynamic gather runs ~2GB/s
            # here, while these read/write the 22MB caches at HBM speed.
            # Caches are flat [L, C*F*B] (see hist_cache0).
            hsm = _onehot_gather(st.small_hist, drop_p)          # [K, C*F*B]
            hlg = _onehot_gather(st.hist_cache, drop_p) - hsm
            sil = st.small_is_left[p_j][:, None]
            hcl = jnp.where(sil, hsm, hlg)
            hcr = jnp.where(sil, hlg, hsm)
            hist_cache = _onehot_scatter(
                st.hist_cache,
                jnp.concatenate([drop_p, drop_r]),
                jnp.concatenate([hcl, hcr], axis=0))

            # install the children's pre-searched best splits
            best = SplitResult(*[
                a.at[drop_p].set(lv[p_j], mode="drop")
                 .at[drop_r].set(rv[p_j], mode="drop")
                for a, lv, rv in zip(st.best, st.bestl, st.bestr)])
            best_is_cat = upd2(st.best_is_cat, st.catl[p_j], st.catr[p_j])
            best_bitset = st.best_bitset.at[drop_p].set(
                st.bitsl[p_j], mode="drop")
            best_bitset = best_bitset.at[drop_r].set(
                st.bitsr[p_j], mode="drop")
            ready = upd2(st.ready, False, False)
            almin, almax, armin, armax = child_bounds(
                bs2, st.leaf_min[p_j], st.leaf_max[p_j])
            leaf_min2 = upd2(st.leaf_min, almin, armin)
            leaf_max2 = upd2(st.leaf_max, almax, armax)
            asets = child_sets(bs2, st.leaf_sets[p_j])
            leaf_sets2 = upd2(st.leaf_sets, asets, asets)
            leaf_forced2 = upd2(st.leaf_forced, st.fidl[p_j], st.fidr[p_j],
                                jnp.int32)
            best_forced2 = upd2(st.best_forced, st.bfl[p_j], st.bfr[p_j])
            feat_used2 = st.feat_used.at[
                jnp.where(appv, bs2.feature, F)].set(True, mode="drop")
            # subtree membership for monotone-intermediate bound refreshes:
            # children inherit the parent leaf's mask and add the new node
            if has_mono and mono_inter:
                pu = st.under[p_j]                               # [K, M]
                setcol = (jnp.arange(M, dtype=jnp.int32)[None, :]
                          == drop_s[:, None])
                under2 = st.under.at[drop_p].set(
                    jnp.where(setcol, jnp.int8(1), pu), mode="drop")
                under2 = under2.at[drop_r].set(
                    jnp.where(setcol, jnp.int8(2), pu), mode="drop")
            else:
                under2 = st.under

            st = st._replace(
                under=under2,
                tree=t,
                leaf_parent_node=upd2(st.leaf_parent_node, s_j, s_j,
                                      jnp.int32),
                leaf_is_left=upd2(st.leaf_is_left,
                                  jnp.ones((KMAX,), bool),
                                  jnp.zeros((KMAX,), bool)),
                leaf_depth=upd2(st.leaf_depth, depth_child, depth_child,
                                jnp.int32),
                leaf_output=upd2(st.leaf_output, bs2.left_output,
                                 bs2.right_output),
                leaf_sum_g=upd2(st.leaf_sum_g, bs2.left_sum_g,
                                bs2.right_sum_g),
                leaf_sum_h=upd2(st.leaf_sum_h, bs2.left_sum_h,
                                bs2.right_sum_h),
                hist_cache=hist_cache, ready=ready,
                leaf_min=leaf_min2, leaf_max=leaf_max2,
                leaf_sets=leaf_sets2,
                best=best, best_is_cat=best_is_cat, best_bitset=best_bitset,
                leaf_forced=leaf_forced2, best_forced=best_forced2,
                feat_used=feat_used2,
            )

            if has_mono and mono_inter:
                # ---- refresh intermediate bounds against CURRENT subtree
                # output extrema (the batched fixpoint of the reference's
                # leaves_to_update propagation, GoUpToFindLeavesToUpdate,
                # monotone_constraints.hpp:625): for an increasing split at
                # node n, every leaf in left(n) is capped above by
                # min(outputs over right(n)) and vice versa. Leaves whose
                # bounds MOVED are re-searched (ready cleared).
                act = jnp.arange(L) < st.tree.num_leaves
                o_min = jnp.where(act, st.leaf_output, jnp.inf)[:, None]
                o_max = jnp.where(act, st.leaf_output, -jnp.inf)[:, None]
                uL = st.under == 1                               # [L, M]
                uR = st.under == 2
                lmax_n = jnp.max(jnp.where(uL, o_max, -jnp.inf), axis=0)
                rmin_n = jnp.min(jnp.where(uR, o_min, jnp.inf), axis=0)
                lmin_n = jnp.min(jnp.where(uL, o_min, jnp.inf), axis=0)
                rmax_n = jnp.max(jnp.where(uR, o_max, -jnp.inf), axis=0)
                node_act = jnp.arange(M) < st.tree.num_leaves - 1
                mono_n = jnp.where(node_act,
                                   meta.monotone[st.tree.split_feature]
                                   .astype(jnp.int32), 0)        # [M]
                capmax = jnp.where(
                    (mono_n > 0)[None, :] & uL, rmin_n[None, :],
                    jnp.where((mono_n < 0)[None, :] & uR, lmin_n[None, :],
                              jnp.inf))
                capmin = jnp.where(
                    (mono_n > 0)[None, :] & uR, lmax_n[None, :],
                    jnp.where((mono_n < 0)[None, :] & uL, rmax_n[None, :],
                              -jnp.inf))
                new_max = jnp.min(capmax, axis=1)                # [L]
                new_min = jnp.max(capmin, axis=1)
                moved = act & ((jnp.abs(new_min - st.leaf_min) > 1e-12)
                               | (jnp.abs(new_max - st.leaf_max) > 1e-12))
                st = st._replace(leaf_min=new_min, leaf_max=new_max,
                                 ready=st.ready & ~moved,
                                 stale=st.stale | moved)

        # ---- SPECULATE selection: top-K unready frontier leaves by gain
        # (post-apply state: fresh children compete immediately)
        budget2 = L - st.tree.num_leaves
        keyed2 = sel_key(st.best.gain, st.best_forced, st.leaf_forced)
        cand_gain = jnp.where(st.ready | st.stale, NEG_INF, keyed2)
        gains, cand = jax.lax.top_k(cand_gain, KMAX)
        cand = cand.astype(jnp.int32)
        valid = (gains > 0.0) & (j_iota < budget2)
        if not cfg.wave_exact and cfg.wave_gain_slack > 0.0:
            # mirror the apply guard (incl. its budget-pressure gate): a
            # leaf the apply rule would block anyway is not worth a
            # histogram slot yet — it re-enters once the frontier's best
            # gain drops to its level. Keeps the slot count paid per tree
            # near the number of splits actually made.
            nval = jnp.sum(valid).astype(jnp.int32)
            guard = gains >= cfg.wave_gain_slack * jnp.max(keyed2)
            if L < 64:
                pressure2 = jnp.bool_(True)
            else:
                pressure2 = 2 * nval >= budget2
            valid &= guard | (j_iota < (nval + 1) // 2) | ~pressure2
        n_cand = jnp.sum(valid).astype(jnp.int32)
        bs = SplitResult(*[x[cand] for x in st.best])

        cand_tbl = jnp.where(valid, cand, -1)
        smaller_is_left = bs.left_count <= bs.right_count    # [K]

        if use_mega or use_apply:
            # the semantic wave table (histogram_pallas.py row layout)
            def gmeta(a, feat):
                return jnp.take(a, feat, mode="clip").astype(jnp.int32)

            tbl16 = jnp.stack([
                app_leaf.astype(jnp.int32),
                bs2.feature.astype(jnp.int32),
                bs2.threshold.astype(jnp.int32),
                bs2.default_left.astype(jnp.int32),
                gmeta(meta.missing_type, bs2.feature),
                gmeta(meta.default_bin, bs2.feature),
                gmeta(meta.num_bins, bs2.feature),
                cand_tbl.astype(jnp.int32),
                bs.feature.astype(jnp.int32),
                bs.threshold.astype(jnp.int32),
                bs.default_left.astype(jnp.int32),
                gmeta(meta.missing_type, bs.feature),
                gmeta(meta.default_bin, bs.feature),
                gmeta(meta.num_bins, bs.feature),
                smaller_is_left.astype(jnp.int32),
                jnp.full((KMAX,), nl0, jnp.int32),
            ])                                               # [16, KMAX]
            if KMAX < 128:
                # pad entries must be INACTIVE: leaf id -1 (0 is a real
                # leaf — the kernel applies every active table entry)
                tbl16 = jnp.pad(tbl16, ((0, 0), (0, 128 - KMAX)),
                                constant_values=-1)
            # a categorical table's kernels decide every entry by its
            # bin-domain bitset, a second operand beside the table
            kargs = (tbl16,)
            if cfg.has_categorical and route != "matrix":
                from .histogram_pallas import wave_cat_words
                kargs = wave_cat_words(tbl16, iscat2, bits2,
                                       st.best_is_cat[cand],
                                       st.best_bitset[cand])
        if use_mega:
            # ---- wave megakernel: relabel + candidate membership + slot
            # histogram in one device pass. The histogram width tracks
            # the CANDIDATE count only (the apply side always walks all
            # 128 table rows — cheap compares); branch 0 skips the
            # contraction when nothing is speculated
            kidx_m = jnp.where(
                n_cand > 0,
                1 + jnp.minimum(
                    jnp.searchsorted(bucket_bounds, n_cand)
                    .astype(jnp.int32), len(buckets) - 1),
                0)
            with jax.named_scope("train/wave_pass"):
                leaf_of_row, hist_wave, *cnt_wave, n_rows = jax.lax.switch(
                    kidx_m, mega_branches, (st.leaf_of_row,) + kargs)
            # (wave-pass calls, their slots, rows contracted) this tree
            k_call = jnp.asarray([0] + buckets, jnp.uint32)[kidx_m]
            st = st._replace(
                leaf_of_row=leaf_of_row,
                tree=st.tree._replace(wave_counts=st.tree.wave_counts
                                      + jnp.stack([jnp.minimum(k_call, 1),
                                                   k_call, psum(n_rows)])))
            slot_small = None
        elif route == "decide":
            # ---- the kernel reads the binned rows and decides each row
            # under its own entry's split alone, so no [K, N] array
            # reaches HBM
            from .histogram_pallas import wave_decide_pallas
            from .histogram import pallas_interpret
            leaf_of_row, slot_small = wave_decide_pallas(
                X_t, st.leaf_of_row, kargs[0], interpret=pallas_interpret(),
                cat=kargs[1] if len(kargs) > 1 else None)
            st = st._replace(leaf_of_row=leaf_of_row)
        elif use_apply:
            # ---- EFB tables: per-(entry, row) go-left
            # decisions are INDEPENDENT of leaf membership, so they are
            # precomputed here as a [128, N] bit matrix in plain XLA
            # (vectorized over entries — bundle unpack and categorical
            # bitsets included), and a slim kernel resolves membership.
            glA = dec_go_left(app_leaf, bs2.feature, bs2.threshold,
                              bs2.default_left, iscat2, bits2)
            glC = dec_go_left(cand_tbl, bs.feature, bs.threshold,
                              bs.default_left, st.best_is_cat[cand],
                              st.best_bitset[cand])
            land_small = glC == smaller_is_left[:, None]
            dec = (glA.astype(jnp.int8)
                   | (land_small.astype(jnp.int8) << 1))     # [KMAX, N]
            if KMAX < 128:
                dec = jnp.pad(dec, ((0, 128 - KMAX), (0, 0)))
            from .histogram_pallas import wave_apply_pallas
            from .histogram import pallas_interpret
            leaf_of_row, slot_small = wave_apply_pallas(
                dec, st.leaf_of_row, tbl16,
                interpret=pallas_interpret())
            st = st._replace(leaf_of_row=leaf_of_row)
        else:
            # ---- portable path: RELABEL applied splits, then evaluate
            # candidate membership on the NEW leaf (elementwise
            # select-chain passes)
            slot_app, in_app, gl_app = table_go_left_bucketed(
                napp, st.leaf_of_row, app_leaf, bs2.feature, bs2.threshold,
                bs2.default_left, iscat2, bits2)
            # right child of applied split j is leaf nl0 + j
            leaf_of_row = jnp.where(in_app & ~gl_app,
                                    nl0 + slot_app, st.leaf_of_row)
            st = st._replace(leaf_of_row=leaf_of_row)

            slot_row, in_cand, gl_cand = table_go_left_bucketed(
                n_cand, leaf_of_row, cand_tbl, bs.feature, bs.threshold,
                bs.default_left, st.best_is_cat[cand], st.best_bitset[cand])

            # smaller child of each candidate (global counts from the split
            # record -> identical on all shards); select-chain instead of a
            # [N]-gather
            sil_row = jnp.zeros((N,), bool)
            for j in range(KMAX):
                sil_row = jnp.where(slot_row == j, smaller_is_left[j],
                                    sil_row)
            in_small = in_cand & (gl_cand == sil_row)
            slot_small = jnp.where(in_small, slot_row, -1)

        # ---- HIST + SEARCH, skipped entirely when no candidates (e.g.
        # the final wave of a tree)
        def spec_branch(st):
            bs_c = bs
            if exact_counts:
                # the candidates' children take their exact in-bag row
                # counts (the smaller child's counted, the larger's the
                # parent's less it), as the reference's data partition
                # gives them; a categorical search's cat_smooth and
                # min_data_per_group steps read them
                if use_mega:
                    small_n = cnt_wave[0]
                else:
                    small_n = jnp.sum(jnp.where(
                        slot_small[None, :]
                        == jnp.arange(KMAX, dtype=jnp.int32)[:, None],
                        cnt_row[None, :], 0.0), axis=1)
                par_n = st.tree.leaf_count[cand].astype(jnp.float32)
                ln = jnp.where(smaller_is_left, psum(small_n),
                               par_n - psum(small_n))
                bs_c = bs._replace(
                    left_count=ln.astype(bs.left_count.dtype),
                    right_count=(par_n - ln).astype(bs.right_count.dtype))
            if use_mega:
                hist_local = hist_wave
            else:
                kidx = jnp.searchsorted(bucket_bounds,
                                        n_cand).astype(jnp.int32)
                kidx = jnp.minimum(kidx, len(buckets) - 1)
                with jax.named_scope("train/wave_pass"):
                    hist_local = jax.lax.switch(kidx, hist_branches,
                                                slot_small)
            if fo:
                if cfg.parallel_hist_mode == "allreduce":
                    # full-histogram psum baseline: every rank receives
                    # the complete summed wave histogram and slices its
                    # own features out locally. Zero-padding commutes
                    # with the sum, so the slice is bitwise equal to the
                    # psum_scatter shard — only the wire profile differs.
                    full = exchange_hist(hist_local, psum, 1)
                    pads = [(0, 0)] * full.ndim
                    pads[2] = (0, Fh_pad - full.shape[2])
                    hist_small = jax.lax.dynamic_slice_in_dim(
                        jnp.pad(full, pads), foff, Fs, 2)
                else:
                    pads = [(0, 0)] * hist_local.ndim
                    pads[2] = (0, Fh_pad - hist_local.shape[2])
                    hist_small = exchange_hist(
                        jnp.pad(hist_local, pads),
                        lambda x: dist.psum_scatter(x, axis=2), 1)
            elif vo:
                hist_small = hist_local     # voting: caches stay local
            elif fp:
                # full rows local: the feature-slice histogram IS global
                hist_small = hist_local
            else:
                hist_small = exchange_hist(hist_local, psum, 1)
            hist_parent = _onehot_gather(
                st.hist_cache, jnp.where(valid, cand, L)
            ).reshape((KMAX,) + hshape)                      # [K, C, F, B]
            hist_large = hist_parent - hist_small
            hist_l = jnp.where(smaller_is_left[:, None, None, None],
                               hist_small, hist_large)
            hist_r = jnp.where(smaller_is_left[:, None, None, None],
                               hist_large, hist_small)

            # best splits of both children of every candidate (2K
            # batched). Monotone-intermediate appends a THIRD block: the
            # STALE leaves' OWN bests re-searched against their REFRESHED
            # bounds (the reference re-searches its leaves_to_update the
            # same way, serial_tree_learner.cpp
            # FindBestSplitsFromHistograms on the repair list). Stale
            # leaves are excluded from child speculation this wave — a
            # changed best would mismatch the speculated child
            # histograms — and re-enter as normal candidates next wave.
            research_own = has_mono and mono_inter
            if research_own:
                rs_gain = jnp.where(st.stale,
                                    jnp.maximum(st.best.gain, 0.0),
                                    NEG_INF)
                _, rs_i = jax.lax.top_k(rs_gain, KMAX)
                rs_i = rs_i.astype(jnp.int32)
                rs_valid = st.stale[rs_i]
                hist_own = _onehot_gather(
                    st.hist_cache, jnp.where(rs_valid, rs_i, L)
                ).reshape((KMAX,) + hshape)
                own = [hist_own]
            else:
                own = []
            hist_lr = jnp.concatenate([hist_l, hist_r] + own, axis=0)

            def cat3(a, b, o):
                return jnp.concatenate([a, b] + ([o] if research_own
                                                 else []))

            sg_lr = cat3(bs.left_sum_g, bs.right_sum_g,
                         st.leaf_sum_g[rs_i] if research_own else None)
            sh_lr = cat3(bs.left_sum_h, bs.right_sum_h,
                         st.leaf_sum_h[rs_i] if research_own else None)
            c_lr = cat3(bs_c.left_count, bs_c.right_count,
                        st.tree.leaf_count[rs_i].astype(
                            bs.left_count.dtype) if research_own
                        else None)
            o_lr = cat3(bs.left_output, bs.right_output,
                        st.leaf_output[rs_i] if research_own else None)
            clmin, clmax, crmin, crmax = child_bounds(
                bs, st.leaf_min[cand], st.leaf_max[cand])
            bmin_lr = cat3(clmin, crmin,
                           st.leaf_min[rs_i] if research_own else None)
            bmax_lr = cat3(clmax, crmax,
                           st.leaf_max[rs_i] if research_own else None)
            csets = child_sets(bs, st.leaf_sets[cand])       # [K, S]
            sets_lr = jnp.concatenate(
                [csets, csets] + ([st.leaf_sets[rs_i]] if research_own
                                  else []), axis=0)
            # children's forced-node ids: candidate's best IS its forced
            # split -> its children continue the forced table (BFS walk)
            if has_forced:
                cfid = st.leaf_forced[cand]
                cforced = st.best_forced[cand]
                cfid_c = jnp.clip(cfid, 0, meta.forced.shape[1] - 1)
                fidl_k = jnp.where(cforced, meta.forced[2, cfid_c], -1)
                fidr_k = jnp.where(cforced, meta.forced[3, cfid_c], -1)
                fid_lr = jnp.concatenate(
                    [fidl_k, fidr_k]
                    + ([st.leaf_forced[rs_i]] if research_own else []))
            else:
                fidl_k = fidr_k = jnp.full((KMAX,), -1, jnp.int32)
                fid_lr = None
            n_batch = (3 if research_own else 2) * KMAX
            if bynode:
                bn_masks = node_masks(
                    jax.random.fold_in(_bn_base,
                                       st.tree.num_waves + 1),
                    n_batch)                              # [nb, F]
            if vo:
                # ---- PV-Tree vote (voting_parallel_tree_learner.cpp):
                # rank features by LOCAL gain, psum the votes, aggregate
                # only the 2k winners' histogram columns
                from .split import per_feature_best_gain
                kv = cfg.voting_top_k
                kv2 = min(2 * kv, F)
                hist_v = to_f32(hist_lr)                  # [2K, C, F, B]
                loc_g = jnp.sum(hist_v[:, 0, 0, :], axis=-1)
                loc_h = jnp.sum(hist_v[:, 1, 0, :], axis=-1)
                # EXACT local child counts: the reference voting learner
                # screens min_data_in_leaf against each shard's TRUE
                # local counts (voting_parallel_tree_learner.cpp local
                # FindBestSplits), so estimating them as
                # loc_h * (global count / global sum_h) skews the local
                # vote whenever hessians skew against counts on a shard.
                # Parent local count by leaf scatter; smaller child's by
                # candidate-slot scatter of the in-bag row indicator.
                leafc_loc = jnp.zeros((L,), jnp.float32).at[
                    jnp.clip(st.leaf_of_row, 0, L - 1)].add(cnt_row)
                par_loc = jnp.where(valid,
                                    leafc_loc[jnp.clip(cand, 0, L - 1)],
                                    0.0)
                if slot_small is None:
                    # mega path fused membership into the kernel; redo it
                    # here (select-chain, voting waves only)
                    slot_v, in_v, gl_v = table_go_left_bucketed(
                        n_cand, st.leaf_of_row, cand_tbl, bs.feature,
                        bs.threshold, bs.default_left,
                        st.best_is_cat[cand], st.best_bitset[cand])
                    sil_v = jnp.zeros((N,), bool)
                    for j in range(KMAX):
                        sil_v = jnp.where(slot_v == j,
                                          smaller_is_left[j], sil_v)
                    slot_small_v = jnp.where(in_v & (gl_v == sil_v),
                                             slot_v, -1)
                else:
                    slot_small_v = slot_small
                small_loc = jnp.zeros((KMAX + 1,), jnp.float32).at[
                    jnp.where(slot_small_v >= 0, slot_small_v, KMAX)
                ].add(cnt_row)[:KMAX]
                loc_c_left = jnp.where(smaller_is_left, small_loc,
                                       par_loc - small_loc)
                loc_c = jnp.concatenate([loc_c_left,
                                         par_loc - loc_c_left])
                hist3 = jax.vmap(synth_count_channel)(hist_v, loc_c, loc_h)
                if bynode:
                    fm_vote = (bn_masks if feature_mask is None
                               else bn_masks & feature_mask[None, :])
                elif feature_mask is not None:
                    fm_vote = jnp.broadcast_to(feature_mask[None, :],
                                               (2 * KMAX, F))
                else:
                    fm_vote = None
                if has_inter:
                    # votes must respect each node's active constraint
                    # sets, or the voted 2k features could all be
                    # unsplittable for that node
                    allowed = (sets_lr.astype(jnp.float32)
                               @ meta.inter_sets.astype(jnp.float32)) > 0
                    fm_vote = (allowed if fm_vote is None
                               else fm_vote & allowed)
                lgains = jax.vmap(
                    lambda h_, g_, hh_, c_, o_, fm_: per_feature_best_gain(
                        h_, g_, hh_, c_, o_, meta, hp, fm_))(
                    hist3, loc_g, loc_h, loc_c, o_lr, fm_vote)  # [2K, F]
                _, topi = jax.lax.top_k(lgains, min(kv, F))
                fin = jnp.isfinite(jnp.take_along_axis(
                    lgains, topi, axis=1))
                iota_f = jnp.arange(F, dtype=jnp.int32)
                votes = jnp.sum(
                    (topi[:, :, None] == iota_f[None, None, :])
                    & fin[:, :, None], axis=1).astype(jnp.float32)
                votes = psum(votes)                       # [2K, F]
                # deterministic tie-break toward lower feature ids so
                # every shard selects the identical voted set
                score = votes * (F + 1) + (F - iota_f)[None, :]
                _, vf = jax.lax.top_k(score, kv2)         # [2K, kv2]
                hv = psum(jnp.take_along_axis(
                    hist_lr, vf[:, None, :, None], axis=2))
                mono_v = meta.monotone[vf] if has_mono else None
                inter_v = (jnp.moveaxis(meta.inter_sets[:, vf], 1, 0)
                           if has_inter else None)        # [2K, S, kv2]
                fmask_v = (jnp.take_along_axis(fm_vote, vf, axis=1)
                           if fm_vote is not None else None)
                s_lr, cat_lr, bits_lr, forced_lr = jax.vmap(search_voted)(
                    hv, sg_lr, sh_lr, c_lr, o_lr, bmin_lr, bmax_lr,
                    sets_lr, meta.num_bins[vf], meta.missing_type[vf],
                    meta.default_bin[vf], mono_v, inter_v, fmask_v)
                # voted-local feature index -> global feature id
                s_lr = s_lr._replace(feature=jnp.take_along_axis(
                    vf, s_lr.feature[:, None], axis=1)[:, 0])
            else:
                xt_rand = (xt_bins(
                    jax.random.fold_in(_xt_base, st.tree.num_waves + 1),
                    n_batch) if xt else None)
                mpf_lr = None
                if use_mpen:
                    d_lr = cat3(st.leaf_depth[cand] + 1,
                                st.leaf_depth[cand] + 1,
                                st.leaf_depth[rs_i] if research_own
                                else None)
                    mpf_lr = mpen_factor(d_lr)
                s_lr, cat_lr, bits_lr, forced_lr = jax.vmap(
                    lambda h_, sg_, sh_, c_, o_, bn_, bx_, st_, fi_, fd_,
                    rd_, mp_:
                    search_sh(h_, sg_, sh_, c_, o_, bn_, bx_, st_, fi_,
                              used_f=st.feat_used, fmask_dyn=fd_,
                              rand_dyn=rd_, mono_pf=mp_))(
                    hist_lr, sg_lr, sh_lr, c_lr, o_lr, bmin_lr, bmax_lr,
                    sets_lr, fid_lr, bn_masks if bynode else None,
                    xt_rand, mpf_lr)
            if fo or fp:
                # map slice-local feature ids to global, then merge the
                # per-shard bests by SELECTION KEY (a forced split must
                # beat other shards' normal bests regardless of gain;
                # SyncUpGlobalBestSplit, parallel_tree_learner.h:210-233)
                s_lr = s_lr._replace(feature=s_lr.feature + foff)
                if use_pmax_sync:
                    # broadcast-free: two pmax rounds on order-encoded
                    # uint32 keys elect the winner per slot (ties on
                    # gain -> lowest feature, identical to the gather
                    # merge's lowest-rank argmax since feature slices
                    # ascend with rank), then ONE masked psum recovers
                    # the unique winner's record bit-exactly
                    from ..parallel.packed import (masked_psum_record,
                                                   pmax_winner_mask)
                    key_gain = s_lr.gain
                    if has_forced:
                        key_gain = jnp.where(forced_lr, 2e18, key_gain)
                    win = pmax_winner_mask(dist, key_gain, s_lr.feature,
                                           s_lr.threshold,
                                           s_lr.default_left, cat_lr)
                    s_lr, cat_lr, bits_lr, forced_lr = masked_psum_record(
                        dist, win, (s_lr, cat_lr, bits_lr, forced_lr))
                else:
                    rec = (tuple(s_lr), cat_lr, bits_lr, forced_lr)
                    allr = jax.tree.map(
                        lambda a: dist.all_gather(a, axis=0, tiled=False),
                        rec)
                    key_all = allr[0][0]                  # [n, 2K] gains
                    if has_forced:
                        key_all = jnp.where(allr[3], 2e18, key_all)
                    pick = jnp.argmax(key_all, axis=0)    # [2K]

                    def take(a):
                        idx = pick.reshape((1,) + pick.shape
                                           + (1,) * (a.ndim - 2))
                        return jnp.take_along_axis(
                            a, jnp.broadcast_to(idx, (1,) + a.shape[1:]),
                            axis=0)[0]

                    s_lr = SplitResult(*[take(a) for a in allr[0]])
                    cat_lr = take(allr[1])
                    bits_lr = take(allr[2])
                    forced_lr = take(allr[3])
            # depth mask applied at store time so the order simulation can
            # use stored gains directly (the own block re-splits the leaf
            # itself: its depth gate is depth < max_depth)
            can = st.leaf_depth[cand] + 1 < max_depth
            can2 = cat3(can, can,
                        st.leaf_depth[rs_i] < max_depth if research_own
                        else None)
            s_lr = s_lr._replace(
                gain=jnp.where(can2, s_lr.gain, NEG_INF))
            forced_lr = forced_lr & can2

            def scat(arr, v, expand=False):
                vv = jnp.where(valid[:, None] if expand else valid, v,
                               arr[cand])
                return arr.at[cand].set(vv, mode="drop")

            st2 = st._replace(
                small_hist=_onehot_scatter(
                    st.small_hist, jnp.where(valid, cand, L),
                    hist_small.reshape(KMAX, -1)),
                small_is_left=scat(st.small_is_left, smaller_is_left),
                ready=scat(st.ready, True),
                bestl=SplitResult(*[scat(a, v[:KMAX])
                                    for a, v in zip(st.bestl, s_lr)]),
                bestr=SplitResult(*[scat(a, v[KMAX:2 * KMAX])
                                    for a, v in zip(st.bestr, s_lr)]),
                catl=scat(st.catl, cat_lr[:KMAX]),
                catr=scat(st.catr, cat_lr[KMAX:2 * KMAX]),
                bitsl=scat(st.bitsl, bits_lr[:KMAX], expand=True),
                bitsr=scat(st.bitsr, bits_lr[KMAX:2 * KMAX], expand=True),
                fidl=scat(st.fidl, fidl_k),
                fidr=scat(st.fidr, fidr_k),
                bfl=scat(st.bfl, forced_lr[:KMAX]),
                bfr=scat(st.bfr, forced_lr[KMAX:2 * KMAX]),
            )
            if exact_counts:
                st2 = st2._replace(best=st2.best._replace(
                    left_count=scat(st2.best.left_count, bs_c.left_count),
                    right_count=scat(st2.best.right_count,
                                     bs_c.right_count)))
            if research_own:
                # install the stale leaves' re-searched bests and clear
                # their staleness (they re-enter as candidates next wave)
                def scat_rs(arr, v, expand=False):
                    vv = jnp.where(rs_valid[:, None] if expand
                                   else rs_valid, v, arr[rs_i])
                    return arr.at[rs_i].set(vv, mode="drop")

                st2 = st2._replace(
                    best=SplitResult(*[scat_rs(a, v[2 * KMAX:])
                                       for a, v in zip(st2.best, s_lr)]),
                    best_is_cat=scat_rs(st2.best_is_cat,
                                        cat_lr[2 * KMAX:]),
                    best_bitset=scat_rs(st2.best_bitset,
                                        bits_lr[2 * KMAX:], expand=True),
                    best_forced=scat_rs(st2.best_forced,
                                        forced_lr[2 * KMAX:]),
                    stale=st2.stale.at[jnp.where(rs_valid, rs_i, L)].set(
                        False, mode="drop"),
                )
            return st2

        st = st._replace(tree=st.tree._replace(
            num_waves=st.tree.num_waves + 1))
        spec_work = n_cand > 0
        if has_mono and mono_inter:
            # stale own re-searches must run even with no candidates
            spec_work = spec_work | jnp.any(st.stale)
        return jax.lax.cond(spec_work, spec_branch, lambda s: s, st)

    def cond(st: _WaveState):
        keyed = sel_key(st.best.gain, st.best_forced, st.leaf_forced)
        return (st.tree.num_leaves < L) & (jnp.max(keyed) > 0.0)

    if L > 1:
        state = jax.lax.while_loop(cond, wave_step, state)

    tree_out = state.tree
    if quant and cfg.quant_renew_leaf and cfg.path_smooth <= 1e-15:
        # RenewIntGradTreeOutput (gradient_discretizer.cpp:210): replace
        # quantized leaf values with outputs from EXACT fp leaf sums —
        # segment sums over leaf_of_row via the slot kernel on a dummy
        # single-bin feature (all mass lands in bin 0)
        dummy = jnp.zeros((1, N), jnp.uint8)
        fp2 = jnp.stack([g, h], axis=0)
        sums = []
        for off in range(0, L, KMAX):
            sl = jnp.where((state.leaf_of_row >= off)
                           & (state.leaf_of_row < off + KMAX),
                           state.leaf_of_row - off, -1)
            hs = psum(build_histogram_slots(dummy, fp2, sl, KMAX, 32,
                                            cfg.rows_per_chunk))
            sums.append(hs[:, :, 0, 0])                  # [KMAX, 2]
        sums = jnp.concatenate(sums, axis=0)[:L]
        sg, sh = sums[:, 0], sums[:, 1]
        # path_smooth is off here: the count and the parent go unread
        lv = leaf_output(sg, sh, hp, None, None)
        ok = (jnp.arange(L) < tree_out.num_leaves) & (sh > 0.0) \
            & (tree_out.num_leaves > 1)
        tree_out = tree_out._replace(
            leaf_value=jnp.where(ok, lv.astype(jnp.float32),
                                 tree_out.leaf_value))

    return tree_out, state.leaf_of_row
