"""Leaf-wise (best-first) tree growth, fully on device.

TPU-native re-design of SerialTreeLearner::Train
(src/treelearner/serial_tree_learner.cpp:183-249) and its CUDA counterpart
CUDASingleGPUTreeLearner::Train (cuda_single_gpu_tree_learner.cpp:170-330):
the entire tree is grown inside ONE jitted computation — a
`lax.fori_loop` over `num_leaves - 1` splits with every buffer statically
sized — so no host synchronization happens per split (the CUDA learner needs
one readback per split; here even that is removed).

Key structural translation (see SURVEY.md §7 design stance):
 - DataPartition's per-leaf index lists (data_partition.hpp:22) become a dense
   `row -> leaf id` vector updated pointwise at each split; histogram masking
   replaces index gathering (static shapes; no scatter).
 - The smaller/larger-leaf histogram subtraction trick is replaced in this
   baseline path by a single fused 6-channel pass that produces BOTH children's
   histograms at once ((grad, hess, count) x (left, right)); the
   compact-gather + subtraction fast path lives in ops/grow_fast.py.
 - Best-split search is the vectorized scan of ops/split.py.
 - When `dist` is set, per-leaf histograms cross the data-parallel mesh axis
   before split search. Under `parallel_hist_mode=allreduce` they are
   `psum`-reduced in full to every rank; under `reduce_scatter` they are
   `psum_scatter`-ed so each rank owns a feature slice, searches only it,
   and the winner syncs broadcast-free via order-encoded pmax keys — the
   reference's ReduceScatter + SyncUpGlobalBestSplit
   (data_parallel_tree_learner.cpp:286-298, parallel_tree_learner.h:210-233)
   riding ICI instead of sockets.

Leaf/node numbering matches Tree::Split (src/io/tree.cpp:60-100): internal
node s is created by split s; the left child keeps leaf id `p`, the right
child becomes new leaf id `s+1`; child pointers store `~leaf` for leaves.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..models.tree import MISSING_NAN, MISSING_ZERO
from .categorical import CatConfig, find_best_split_categorical
from .histogram import build_histogram
from .split import (NEG_INF, FeatureMeta, SplitHyperParams, SplitResult,
                    find_best_split, root_totals, synth_count_channel)


class GrowConfig(NamedTuple):
    """Static configuration for the grower (hashable; part of the jit key)."""
    num_leaves: int
    max_depth: int              # <=0 means unlimited
    min_data_in_leaf: float
    min_sum_hessian_in_leaf: float
    lambda_l1: float
    lambda_l2: float
    max_delta_step: float
    min_gain_to_split: float
    path_smooth: float
    num_bins_padded: int        # B: padded bin axis
    rows_per_chunk: int = 8192
    # bin-width-tiered histogram path (ops/histogram_tiered.py,
    # docs/PERF.md): per-STORAGE-COLUMN bin counts in storage order
    # (empty = legacy uniform kernel) and the implementation selector
    # ("auto" | "legacy" | "tiered" | "tiered_hilo" —
    # config.histogram_impl, possibly overridden by runtime/autotune.py)
    hist_tiers: tuple = ()
    hist_impl: str = "auto"
    # categorical split search (reference: config.h cat_* params)
    has_categorical: bool = False
    max_cat_to_onehot: int = 4
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    min_data_per_group: float = 100.0
    # wave grower order semantics: False = apply ready leaves per wave in
    # gain order (TPU-native batched frontier, ~log L histogram passes per
    # tree); True = strict leaf-wise priority order (blocks on leaves
    # whose child histograms aren't speculated yet; ~O(chain) passes)
    wave_exact: bool = False
    # batched-order guard: a ready leaf only splits in this wave if its
    # gain >= wave_gain_slack * (best gain anywhere in the frontier,
    # including not-yet-ready children). 0 = split everything ready;
    # higher values approach strict leaf-wise order at the cost of more
    # waves
    wave_gain_slack: float = 0.0
    # quantized-gradient training (reference: gradient_discretizer.cpp,
    # config.h:627-646): int8 grad/hess with per-tree scales + stochastic
    # rounding, exact int32 histograms on the int8 MXU path
    use_quantized_grad: bool = False
    num_grad_quant_bins: int = 4
    stochastic_rounding: bool = True
    quant_renew_leaf: bool = False
    # EFB (data/dataset.py:_build_bundles): X_t holds BUNDLE columns;
    # static per-ORIGINAL-feature maps unpack them in the row pass, and
    # meta.bundle_expand re-slices bundle histograms per feature at
    # search time. Empty tuples = no bundling.
    bundle_col: tuple = ()      # orig feature -> bundle column
    bundle_off: tuple = ()      # offset in the bundle, -1 = raw singleton
    bundle_nb: tuple = ()       # orig feature num_bin
    bundle_db: tuple = ()       # orig feature default bin

    # data-parallel mesh size; >1 enables reduce-scatter feature ownership
    # in the wave grower (data_parallel_tree_learner.cpp:72-122)
    n_shards: int = 1

    # CEGB (cost-effective gradient boosting,
    # cost_effective_gradient_boosting.hpp:81 DeltaGain): gain penalty
    # tradeoff * (penalty_split * leaf_count + coupled[f] * first-use)
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0

    # voting-parallel (PV-Tree, voting_parallel_tree_learner.cpp): each
    # shard proposes its top-k features by LOCAL gain, a psum vote picks
    # 2k global candidates, and only those features' histogram columns
    # are aggregated. 0 = off (full data-parallel reduction).
    voting_top_k: int = 0

    # per-node column sampling (ColSampler::GetByNode,
    # col_sampler.hpp:208): each prospective split samples
    # max(1, fraction * F) features, deterministically keyed by
    # (seed, wave, child) so every shard draws the same mask
    feature_fraction_bynode: float = 1.0

    # extra_trees (Config::extra_trees): every numerical-feature search
    # considers ONE uniformly drawn threshold per feature
    # (feature_histogram.hpp:203-207), keyed by (extra_seed, node) so
    # shards agree
    extra_trees: bool = False
    extra_seed: int = 6

    # monotone constraints (monotone_constraints.hpp): "basic" separates
    # children at the output midpoint; "intermediate" bounds each child by
    # its sibling's actual output, with bounds refreshed against current
    # subtree output extrema every wave. monotone_penalty scales the gain
    # of splits on monotone features by depth
    # (ComputeMonotoneSplitGainPenalty, :358)
    monotone_method: str = "basic"
    monotone_penalty: float = 0.0

    # feature-parallel learner (feature_parallel_tree_learner.cpp:23-84):
    # every shard holds ALL rows; features partition per shard; only the
    # tiny split records cross the wire (SyncUpGlobalBestSplit)
    feature_parallel: bool = False

    # data-parallel histogram exchange (docs/PERF.md §Communication):
    # "allreduce" psums the full per-leaf histogram to every rank (this
    # grower then searches every feature; the wave grower slices its
    # owned features out of the full buffer and merges as under
    # reduce_scatter, so its trees never depend on the mode);
    # "reduce_scatter" exchanges via psum_scatter so each rank owns a
    # contiguous feature slice (data_parallel_tree_learner.cpp:286-298),
    # searches only its slice, and the winner is recovered broadcast-free
    # with order-encoded pmax keys whose tie order matches the mode's
    # full-scan semantics (parallel/packed.py). "auto" keeps each
    # grower's default (wave: reduce-scatter ownership; serial:
    # allreduce) unless the runtime autotuner resolves it
    # (runtime/autotune.py).
    parallel_hist_mode: str = "auto"

    @property
    def bundled(self) -> bool:
        return len(self.bundle_col) > 0

    @property
    def hp(self) -> SplitHyperParams:
        return SplitHyperParams(
            min_data_in_leaf=self.min_data_in_leaf,
            min_sum_hessian_in_leaf=self.min_sum_hessian_in_leaf,
            lambda_l1=self.lambda_l1,
            lambda_l2=self.lambda_l2,
            max_delta_step=self.max_delta_step,
            min_gain_to_split=self.min_gain_to_split,
            path_smooth=self.path_smooth,
        )

    @property
    def cat_words(self) -> int:
        """W: uint32 words per bin-bitset."""
        return max((self.num_bins_padded + 31) // 32, 1)

    @property
    def cat(self) -> CatConfig:
        return CatConfig(
            max_cat_to_onehot=self.max_cat_to_onehot,
            max_cat_threshold=self.max_cat_threshold,
            cat_l2=self.cat_l2,
            cat_smooth=self.cat_smooth,
            min_data_per_group=self.min_data_per_group,
            num_bitset_words=self.cat_words,
        )


class DeviceTree(NamedTuple):
    """Grown tree, device-resident (analog of CUDATree, cuda_tree.hpp:29)."""
    num_leaves: jnp.ndarray        # i32 scalar: actual leaves grown
    split_feature: jnp.ndarray     # [M] i32 (inner feature index)
    threshold_bin: jnp.ndarray     # [M] i32
    default_left: jnp.ndarray      # [M] bool
    split_gain: jnp.ndarray        # [M] f32
    left_child: jnp.ndarray        # [M] i32 (negative = ~leaf)
    right_child: jnp.ndarray       # [M] i32
    internal_value: jnp.ndarray    # [M] f32
    internal_weight: jnp.ndarray   # [M] f32
    internal_count: jnp.ndarray    # [M] i32
    leaf_value: jnp.ndarray        # [L] f32 (pre-shrinkage)
    leaf_weight: jnp.ndarray       # [L] f32
    leaf_count: jnp.ndarray        # [L] i32
    split_parent_leaf: jnp.ndarray  # [M] i32: which leaf each split divided
    split_is_cat: jnp.ndarray      # [M] bool: categorical (bitset) split
    split_cat_bitset: jnp.ndarray  # [M, W] u32: left-set over bins
    num_waves: jnp.ndarray         # i32: histogram waves used (diagnostic,
    #                                maintained by the wave grower; the
    #                                serial growers leave it 0)


class _LoopState(NamedTuple):
    tree: DeviceTree
    leaf_of_row: jnp.ndarray       # [N] i32
    leaf_parent_node: jnp.ndarray  # [L] i32 (-1 = root)
    leaf_is_left: jnp.ndarray      # [L] bool
    leaf_depth: jnp.ndarray        # [L] i32
    leaf_output: jnp.ndarray       # [L] f32 (current raw outputs)
    leaf_sum_g: jnp.ndarray        # [L] f32
    leaf_sum_h: jnp.ndarray        # [L] f32
    best: SplitResult              # cached best split per leaf, [L] fields
    best_is_cat: jnp.ndarray       # [L] bool
    best_bitset: jnp.ndarray       # [L, W] u32
    done: jnp.ndarray              # bool scalar


def _root_tree(L: int, W: int, root_h, root_c) -> DeviceTree:
    """The one-leaf tree every grower starts from: the root's hessian
    total and in-bag row count on leaf 0, all else zero."""
    M = max(L - 1, 1)
    return DeviceTree(
        num_leaves=jnp.asarray(1, jnp.int32),
        split_feature=jnp.zeros((M,), jnp.int32),
        threshold_bin=jnp.zeros((M,), jnp.int32),
        default_left=jnp.zeros((M,), bool),
        split_gain=jnp.zeros((M,), jnp.float32),
        left_child=jnp.zeros((M,), jnp.int32),
        right_child=jnp.zeros((M,), jnp.int32),
        internal_value=jnp.zeros((M,), jnp.float32),
        internal_weight=jnp.zeros((M,), jnp.float32),
        internal_count=jnp.zeros((M,), jnp.int32),
        # leaf 0 stays 0.0 until a split sets it: a no-split tree must be a
        # constant-zero tree (AsConstantTree(0), gbdt.cpp:443), NOT the root
        # output
        leaf_value=jnp.zeros((L,), jnp.float32),
        leaf_weight=jnp.zeros((L,), jnp.float32).at[0].set(root_h),
        leaf_count=jnp.zeros((L,), jnp.int32).at[0].set(
            root_c.astype(jnp.int32)),
        split_parent_leaf=jnp.zeros((M,), jnp.int32),
        split_is_cat=jnp.zeros((M,), bool),
        split_cat_bitset=jnp.zeros((M, W), jnp.uint32),
        num_waves=jnp.asarray(0, jnp.int32),
    )


def _empty_split_cache(L: int) -> SplitResult:
    z = jnp.zeros((L,), jnp.float32)
    return SplitResult(
        gain=jnp.full((L,), NEG_INF, jnp.float32),
        feature=jnp.zeros((L,), jnp.int32),
        threshold=jnp.zeros((L,), jnp.int32),
        default_left=jnp.zeros((L,), bool),
        left_sum_g=z, left_sum_h=z, left_count=z,
        right_sum_g=z, right_sum_h=z, right_count=z,
        left_output=z, right_output=z,
    )


def _set_cache(cache: SplitResult, idx, res: SplitResult,
               valid) -> SplitResult:
    return SplitResult(*[
        c.at[idx].set(jnp.where(valid, r, c[idx]))
        for c, r in zip(cache, res)])


def grow_tree(
    X_t: jnp.ndarray,            # [F, N] binned, feature-major
    grad: jnp.ndarray,           # [N] f32
    hess: jnp.ndarray,           # [N] f32
    in_bag: jnp.ndarray,         # [N] f32 (0/1 bagging mask; GOSS weights)
    meta: FeatureMeta,
    cfg: GrowConfig,
    feature_mask: Optional[jnp.ndarray] = None,  # [F] bool per-tree sampling
    dist: Optional[object] = None,  # parallel.DistContext for data-parallel
) -> tuple[DeviceTree, jnp.ndarray]:
    """Grow one tree; returns (DeviceTree, leaf_of_row).

    With `dist`, histograms (the root's totals with them) and the root
    count are psum-reduced over the mesh data axis, making every device
    grow the IDENTICAL tree on its row shard —
    the invariant of the reference's data-parallel learner (SURVEY.md §3.4).
    """
    F, N = X_t.shape
    L = cfg.num_leaves
    B = cfg.num_bins_padded
    hp = cfg.hp
    max_depth = cfg.max_depth if cfg.max_depth > 0 else 10**9

    def psum(x):
        return dist.psum(x) if dist is not None else x

    # ---- reduce-scatter feature ownership (parallel_hist_mode=
    # reduce_scatter; data_parallel_tree_learner.cpp:286-298): per-leaf
    # histograms are exchanged via psum_scatter so each rank receives
    # only the summed slice of the features it owns (offset-contiguous;
    # docs/PARITY.md §Feature-slice ownership), the split scan runs on
    # that slice against sliced metadata, and the global winner is
    # recovered on every rank with order-encoded pmax keys + one masked
    # psum (SyncUpGlobalBestSplit without the record broadcast;
    # parallel/packed.py). EFB-bundled storage keeps the allreduce path:
    # bundle histograms are re-sliced per ORIGINAL feature at search
    # time, which does not commute with slicing storage columns.
    rs_on = (dist is not None and cfg.n_shards > 1
             and cfg.parallel_hist_mode == "reduce_scatter"
             and not cfg.bundled and not cfg.feature_parallel)
    if rs_on:
        from ..parallel.packed import masked_psum_record, pmax_winner_mask
        from ..utils import round_up
        nsh = cfg.n_shards
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

        # padded features get num_bins=0: every bin invalid -> -inf gain
        meta_use = meta._replace(
            num_bins=_slice_f(meta.num_bins, 0),
            missing_type=_slice_f(meta.missing_type, 0),
            default_bin=_slice_f(meta.default_bin, 0),
            is_categorical=_slice_f(meta.is_categorical, 0),
            monotone=_slice_f(meta.monotone, 0),
            inter_sets=(_slice_f(meta.inter_sets, 1)
                        if meta.inter_sets is not None else None),
            cegb_coupled=_slice_f(meta.cegb_coupled, 0),
        )
        fmask_use = (_slice_f(feature_mask, 0)
                     if feature_mask is not None else None)

        def exchange(hist):
            """[..., F, B] full local histogram -> [..., Fs, B] summed
            owned slice (one reduce-scatter; (k-1)/k of the allreduce
            ring bytes)."""
            pads = [(0, 0)] * hist.ndim
            pads[-2] = (0, Fh_pad - F)
            return dist.psum_scatter(jnp.pad(hist, pads),
                                     axis=hist.ndim - 2)
    else:
        meta_use, fmask_use = meta, feature_mask

        def exchange(hist):
            return psum(hist)

    g = grad.astype(jnp.float32) * in_bag
    h = hess.astype(jnp.float32) * in_bag
    # in-bag ROW indicator for the exact root count (GOSS amplification
    # rides only on g/h in the reference, goss.hpp)
    cnt_row = (in_bag > 0).astype(jnp.float32)

    def hist_for_children(leaf_l, leaf_r, leaf_of_row):
        """One fused pass: histograms for both children ((g,h) x (l,r)).

        g/h already carry the in_bag multiplier (out-of-bag rows are 0, GOSS
        rows amplified ONCE) — the leaf masks must stay plain indicators or
        the amplification would square. Histogram entries are (grad, hess)
        only, matching the reference layout (bin.h:40); counts are
        synthesized at search time via cnt_factor."""
        ind_l = (leaf_of_row == leaf_l).astype(jnp.float32)
        ind_r = (leaf_of_row == leaf_r).astype(jnp.float32)
        vals = jnp.stack([g * ind_l, h * ind_l,
                          g * ind_r, h * ind_r],
                         axis=0)                                 # [4, N]
        hist4 = build_histogram(X_t, vals, B, cfg.rows_per_chunk,
                                tiers=cfg.hist_tiers, impl=cfg.hist_impl)
        hist4 = exchange(hist4)
        return hist4[:2], hist4[2:]

    W = cfg.cat_words

    def search(hist, sum_g, sum_h, count, out):
        """Best split over numerical + categorical features
        (FindBestThreshold dispatch, feature_histogram.hpp:166-178).
        `hist` arrives [2, F, B] (the rank's owned [2, Fs, B] slice under
        reduce-scatter); the count channel is synthesized via the
        reference's cnt_factor (feature_histogram.hpp:529,844)."""
        hist = synth_count_channel(hist, count, sum_h)
        num = find_best_split(hist, sum_g, sum_h, count, out, meta_use, hp,
                              fmask_use)
        nob = jnp.zeros((W,), jnp.uint32)
        if not cfg.has_categorical:
            res, use_cat, bits = num, jnp.zeros((), bool), nob
        else:
            catr, bitset = find_best_split_categorical(
                hist, sum_g, sum_h, count, out, meta_use, hp, cfg.cat,
                fmask_use)
            use_cat = catr.gain > num.gain
            res = SplitResult(*[
                jnp.where(use_cat, cv, nv) for cv, nv in zip(catr, num)])
            bits = jnp.where(use_cat, bitset, nob)
        if rs_on:
            # slice-local feature id -> global, then broadcast-free
            # winner election: two pmax rounds on order-encoded uint32
            # keys and ONE masked psum recovering the unique winner's
            # record bit-exactly (candidate features are disjoint
            # across ranks). scan_order: gain ties must resolve exactly
            # as the full-search allreduce path does — numerical over
            # categorical, then default direction, then lowest feature
            # — or an exact tie straddling two ranks' slices would grow
            # different trees under the two modes.
            res = res._replace(feature=res.feature + foff)
            mask = pmax_winner_mask(dist, res.gain, res.feature,
                                    res.threshold, res.default_left,
                                    use_cat, scan_order=True)
            res, use_cat, bits = masked_psum_record(
                dist, mask, (res, use_cat, bits))
        return res, use_cat, bits

    # ---- root (BeforeTrain: serial_tree_learner.cpp:292-342)
    vals0 = jnp.stack([g, h], axis=0)
    hist_root = exchange(build_histogram(X_t, vals0, B, cfg.rows_per_chunk,
                                         tiers=cfg.hist_tiers,
                                         impl=cfg.hist_impl))
    root_g, root_h, root_c, root_out = root_totals(
        hist_root, cnt_row, hp, psum, owner=(foff == 0) if rs_on else None)
    root_split, root_is_cat, root_bitset = search(
        hist_root, root_g, root_h, root_c, root_out)
    root_split = root_split._replace(
        gain=jnp.where(max_depth >= 1, root_split.gain, NEG_INF))

    tree = _root_tree(L, W, root_h, root_c)
    cache = _set_cache(_empty_split_cache(L), 0, root_split, True)
    state = _LoopState(
        tree=tree,
        leaf_of_row=jnp.zeros((N,), jnp.int32),
        leaf_parent_node=jnp.full((L,), -1, jnp.int32),
        leaf_is_left=jnp.zeros((L,), bool),
        leaf_depth=jnp.zeros((L,), jnp.int32),
        leaf_output=jnp.zeros((L,), jnp.float32).at[0].set(root_out),
        leaf_sum_g=jnp.zeros((L,), jnp.float32).at[0].set(root_g),
        leaf_sum_h=jnp.zeros((L,), jnp.float32).at[0].set(root_h),
        best=cache,
        best_is_cat=jnp.zeros((L,), bool).at[0].set(root_is_cat),
        best_bitset=jnp.zeros((L, W), jnp.uint32).at[0].set(root_bitset),
        done=jnp.asarray(False),
    )

    def split_once(s, st: _LoopState) -> _LoopState:
        """One split (the reference's `for split ...` body,
        serial_tree_learner.cpp:222-240)."""
        t = st.tree
        p = jnp.argmax(st.best.gain).astype(jnp.int32)
        bs = SplitResult(*[a[p] for a in st.best])
        bs_is_cat = st.best_is_cat[p]
        bs_bitset = st.best_bitset[p]                         # [W]
        valid = (bs.gain > 0.0) & ~st.done
        new_leaf = (s + 1).astype(jnp.int32)

        # -- record internal node s
        def rec(arr, v):
            return arr.at[s].set(jnp.where(valid, v, arr[s]))

        t = t._replace(
            split_feature=rec(t.split_feature, bs.feature),
            threshold_bin=rec(t.threshold_bin, bs.threshold),
            default_left=rec(t.default_left, bs.default_left),
            split_gain=rec(t.split_gain, bs.gain),
            left_child=rec(t.left_child, ~p),
            right_child=rec(t.right_child, ~new_leaf),
            internal_value=rec(t.internal_value, st.leaf_output[p]),
            internal_weight=rec(t.internal_weight, st.leaf_sum_h[p]),
            internal_count=rec(t.internal_count, t.leaf_count[p]),
            split_parent_leaf=rec(t.split_parent_leaf, p),
            split_is_cat=rec(t.split_is_cat, bs_is_cat),
            split_cat_bitset=t.split_cat_bitset.at[s].set(
                jnp.where(valid, bs_bitset, t.split_cat_bitset[s])),
            num_leaves=t.num_leaves + valid.astype(jnp.int32),
        )
        # -- fix the pointer that used to reference leaf p
        prev = st.leaf_parent_node[p]
        prev_i = jnp.maximum(prev, 0)
        fix = valid & (prev >= 0)
        t = t._replace(
            left_child=t.left_child.at[prev_i].set(
                jnp.where(fix & st.leaf_is_left[p], s, t.left_child[prev_i])),
            right_child=t.right_child.at[prev_i].set(
                jnp.where(fix & ~st.leaf_is_left[p], s,
                          t.right_child[prev_i])))

        # -- partition update (DataPartition::Split analog,
        #    data_partition.hpp:102): rows of leaf p re-tagged left/right
        col = jnp.take(X_t, bs.feature, axis=0).astype(jnp.int32)   # [N]
        mt = meta.missing_type[bs.feature]
        is_missing = ((mt == MISSING_ZERO)
                      & (col == meta.default_bin[bs.feature])) | \
                     ((mt == MISSING_NAN)
                      & (col == meta.num_bins[bs.feature] - 1))
        go_left_num = jnp.where(is_missing, bs.default_left,
                                col <= bs.threshold)
        # categorical: bitset membership (Tree::CategoricalDecision analog)
        words = bs_bitset[jnp.clip(col >> 5, 0, W - 1)]       # [N] u32
        go_left_cat = ((words >> (col & 31).astype(jnp.uint32)) & 1) == 1
        go_left = jnp.where(bs_is_cat, go_left_cat, go_left_num)
        in_p = st.leaf_of_row == p
        leaf_of_row = jnp.where(valid & in_p & ~go_left, new_leaf,
                                st.leaf_of_row)

        # -- exact child counts at split time (update_cnt=true,
        #    serial_tree_learner.cpp:796-799): the true partition count
        #    feeds the tree metadata and the children's parent count below;
        #    per-bin counts inside the split scan stay cnt_factor-
        #    synthesized (synth_count_channel), matching the reference.
        #    t.leaf_count[p] still holds the parent's count here.
        n_left = psum(jnp.sum(cnt_row * (in_p & go_left).astype(jnp.float32)))
        bs = bs._replace(
            left_count=n_left,
            right_count=t.leaf_count[p].astype(jnp.float32) - n_left)

        # -- per-leaf bookkeeping
        depth_child = st.leaf_depth[p] + 1
        leaf_parent_node = st.leaf_parent_node.at[p].set(
            jnp.where(valid, s, st.leaf_parent_node[p]))
        leaf_parent_node = leaf_parent_node.at[new_leaf].set(
            jnp.where(valid, s, leaf_parent_node[new_leaf]))
        leaf_is_left = st.leaf_is_left.at[p].set(
            jnp.where(valid, True, st.leaf_is_left[p]))
        leaf_is_left = leaf_is_left.at[new_leaf].set(
            jnp.where(valid, False, leaf_is_left[new_leaf]))
        leaf_depth = st.leaf_depth.at[p].set(
            jnp.where(valid, depth_child, st.leaf_depth[p]))
        leaf_depth = leaf_depth.at[new_leaf].set(
            jnp.where(valid, depth_child, leaf_depth[new_leaf]))

        def upd(arr, l_val, r_val, cast=None):
            lv = l_val if cast is None else l_val.astype(cast)
            rv = r_val if cast is None else r_val.astype(cast)
            arr = arr.at[p].set(jnp.where(valid, lv, arr[p]))
            return arr.at[new_leaf].set(jnp.where(valid, rv, arr[new_leaf]))

        t = t._replace(
            leaf_value=upd(t.leaf_value, bs.left_output, bs.right_output),
            leaf_weight=upd(t.leaf_weight, bs.left_sum_h, bs.right_sum_h),
            leaf_count=upd(t.leaf_count, bs.left_count, bs.right_count,
                           jnp.int32),
        )
        leaf_output = upd(st.leaf_output, bs.left_output, bs.right_output)
        leaf_sum_g = upd(st.leaf_sum_g, bs.left_sum_g, bs.right_sum_g)
        leaf_sum_h = upd(st.leaf_sum_h, bs.left_sum_h, bs.right_sum_h)

        # -- histograms + split search for both children
        def compute_children(_):
            hist_l, hist_r = hist_for_children(p, new_leaf, leaf_of_row)
            can = depth_child < max_depth
            sl, cl, bl = search(hist_l, bs.left_sum_g, bs.left_sum_h,
                                bs.left_count, bs.left_output)
            sr, cr, br = search(hist_r, bs.right_sum_g, bs.right_sum_h,
                                bs.right_count, bs.right_output)
            sl = sl._replace(gain=jnp.where(can, sl.gain, NEG_INF))
            sr = sr._replace(gain=jnp.where(can, sr.gain, NEG_INF))
            return sl, cl, bl, sr, cr, br

        def skip_children(_):
            zero = _empty_split_cache(1)
            one = SplitResult(*[a[0] for a in zero])
            nocat = jnp.zeros((), bool)
            nobits = jnp.zeros((W,), jnp.uint32)
            return one, nocat, nobits, one, nocat, nobits

        sl, cl, bl, sr, cr, br = jax.lax.cond(
            valid, compute_children, skip_children, None)
        best = _set_cache(st.best, p, sl, valid)
        best = _set_cache(best, new_leaf, sr, valid)
        best_is_cat = st.best_is_cat.at[p].set(
            jnp.where(valid, cl, st.best_is_cat[p]))
        best_is_cat = best_is_cat.at[new_leaf].set(
            jnp.where(valid, cr, best_is_cat[new_leaf]))
        best_bitset = st.best_bitset.at[p].set(
            jnp.where(valid, bl, st.best_bitset[p]))
        best_bitset = best_bitset.at[new_leaf].set(
            jnp.where(valid, br, best_bitset[new_leaf]))

        return _LoopState(
            tree=t, leaf_of_row=leaf_of_row,
            leaf_parent_node=leaf_parent_node, leaf_is_left=leaf_is_left,
            leaf_depth=leaf_depth, leaf_output=leaf_output,
            leaf_sum_g=leaf_sum_g, leaf_sum_h=leaf_sum_h,
            best=best, best_is_cat=best_is_cat, best_bitset=best_bitset,
            done=st.done | ~valid)

    if L > 1:
        state = jax.lax.fori_loop(0, L - 1, split_once, state)
    return state.tree, state.leaf_of_row
