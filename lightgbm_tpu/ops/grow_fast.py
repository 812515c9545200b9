"""Compacted leaf-wise growth: the fast path of the tree grower.

The baseline grower (ops/grow.py) re-scans ALL rows for every split with a
leaf mask — O(num_leaves x N) histogram work per tree. This module is the
TPU-native re-design of the reference's real data layout:

  * DataPartition (src/treelearner/data_partition.hpp:22) keeps `indices_`
    grouped by leaf with (leaf_start, leaf_count); splitting a leaf permutes
    only that leaf's index range. Here: a device-resident `order` [N]
    permutation + leaf_start/leaf_count arrays; the per-split permutation is
    a stable cumsum scatter inside a power-of-2 bucket window.
  * The smaller-child + histogram-subtraction trick
    (SerialTreeLearner::BeforeFindBestSplit, serial_tree_learner.cpp:344:
    construct only the smaller leaf's histogram, derive the sibling by
    parent - smaller): a per-leaf histogram cache [L, F, B, 3] plays the
    reference's HistogramPool (feature_histogram.hpp:1368), and only the
    smaller child is scanned — over its OWN contiguous rows, not all N.

XLA needs static shapes, so dynamic leaf sizes are padded to power-of-2
buckets and dispatched with `lax.switch` (one branch per bucket size, each
traced once). Per-tree histogram work drops from (L-1) x N row-scans to
roughly sum over splits of pow2(count(parent)) ~ 2 N log2(L).

Data-parallel: `order` and the buckets are per-shard and shards MAY take
different `lax.switch` branches — the branches are deliberately
collective-free (the child-histogram psum happens after the switch), so no
cross-device sync of the bucket index is needed. Child histograms are
psum-reduced exactly like the baseline path (SURVEY.md §3.4).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .grow import (DeviceTree, GrowConfig, _empty_split_cache, _root_tree,
                   _set_cache)
from .histogram import build_histogram
from ..models.tree import MISSING_NAN, MISSING_ZERO
from .split import (NEG_INF, FeatureMeta, SplitResult, find_best_split,
                    root_totals, synth_count_channel)
from .categorical import find_best_split_categorical

_MIN_BUCKET = 256


def _bucket_sizes(n: int):
    """Hybrid bucket ladder capped at n.

    Large windows cost gather volume -> tight x2 steps near n; small
    windows cost mostly per-branch dispatch overhead -> coarse x4 steps
    below n/16 (padding 2048-row windows is cheap, another switch branch
    is not).
    """
    sizes = [n]
    s = n // 2
    while s >= max(_MIN_BUCKET, 2048):
        sizes.append(s)
        s = s // 2 if s > n // 16 else s // 4
    if sizes[-1] > _MIN_BUCKET:
        sizes.append(_MIN_BUCKET)
    return sorted(set(sizes))


class _FastState(NamedTuple):
    tree: DeviceTree
    order: jnp.ndarray             # [N] i32: rows grouped by leaf
    leaf_start: jnp.ndarray        # [L] i32 (local/shard-relative)
    leaf_count: jnp.ndarray        # [L] i32 (local rows in shard)
    leaf_parent_node: jnp.ndarray  # [L] i32
    leaf_is_left: jnp.ndarray      # [L] bool
    leaf_depth: jnp.ndarray        # [L] i32
    leaf_output: jnp.ndarray       # [L] f32
    leaf_sum_g: jnp.ndarray        # [L] f32
    leaf_sum_h: jnp.ndarray        # [L] f32
    hist_cache: jnp.ndarray        # [L, 3, F, B] f32 (global hists)
    best: SplitResult
    best_is_cat: jnp.ndarray
    best_bitset: jnp.ndarray
    done: jnp.ndarray


def grow_tree_fast(
    X_t: jnp.ndarray,            # [F, N] binned, feature-major
    grad: jnp.ndarray,           # [N] f32
    hess: jnp.ndarray,           # [N] f32
    in_bag: jnp.ndarray,         # [N] f32
    meta: FeatureMeta,
    cfg: GrowConfig,
    feature_mask: Optional[jnp.ndarray] = None,
    dist: Optional[object] = None,
) -> tuple[DeviceTree, jnp.ndarray]:
    """Compacted leaf-wise growth; same contract as ops/grow.py:grow_tree."""
    F, N = X_t.shape
    L = cfg.num_leaves
    B = cfg.num_bins_padded
    W = cfg.cat_words
    hp = cfg.hp
    max_depth = cfg.max_depth if cfg.max_depth > 0 else 10**9

    def psum(x):
        return dist.psum(x) if dist is not None else x

    g = grad.astype(jnp.float32) * in_bag
    h = hess.astype(jnp.float32) * in_bag
    # count channel = in-bag ROW indicator (GOSS amplification rides only
    # on g/h in the reference, goss.hpp; counts stay true row counts)
    cnt_row = (in_bag > 0).astype(jnp.float32)

    def search(hist, sum_g, sum_h, count, out):
        # hist arrives [2, F, B] (grad, hess); counts synthesize via the
        # reference's cnt_factor (feature_histogram.hpp:529,844)
        hist = synth_count_channel(hist, count, sum_h)
        num = find_best_split(hist, sum_g, sum_h, count, out, meta, hp,
                              feature_mask)
        if not cfg.has_categorical:
            return num, jnp.zeros((), bool), jnp.zeros((W,), jnp.uint32)
        catr, bitset = find_best_split_categorical(
            hist, sum_g, sum_h, count, out, meta, hp, cfg.cat, feature_mask)
        use_cat = catr.gain > num.gain
        merged = SplitResult(*[
            jnp.where(use_cat, cv, nv) for cv, nv in zip(catr, num)])
        return merged, use_cat, jnp.where(use_cat, bitset,
                                          jnp.zeros((W,), jnp.uint32))

    # ---- root
    vals0 = jnp.stack([g, h], axis=0)
    hist_root = psum(build_histogram(X_t, vals0, B, cfg.rows_per_chunk,
                                     tiers=cfg.hist_tiers,
                                     impl=cfg.hist_impl))
    root_g, root_h, root_c, root_out = root_totals(hist_root, cnt_row, hp,
                                                   psum)
    root_split, root_is_cat, root_bitset = search(
        hist_root, root_g, root_h, root_c, root_out)
    root_split = root_split._replace(
        gain=jnp.where(max_depth >= 1, root_split.gain, NEG_INF))

    tree = _root_tree(L, W, root_h, root_c)
    hist_cache = jnp.zeros((L, 2, F, B), jnp.float32).at[0].set(hist_root)
    state = _FastState(
        tree=tree,
        order=jnp.arange(N, dtype=jnp.int32),
        leaf_start=jnp.zeros((L,), jnp.int32),
        leaf_count=jnp.zeros((L,), jnp.int32).at[0].set(N),
        leaf_parent_node=jnp.full((L,), -1, jnp.int32),
        leaf_is_left=jnp.zeros((L,), bool),
        leaf_depth=jnp.zeros((L,), jnp.int32),
        leaf_output=jnp.zeros((L,), jnp.float32).at[0].set(root_out),
        leaf_sum_g=jnp.zeros((L,), jnp.float32).at[0].set(root_g),
        leaf_sum_h=jnp.zeros((L,), jnp.float32).at[0].set(root_h),
        hist_cache=hist_cache,
        best=_set_cache(_empty_split_cache(L), 0, root_split, True),
        best_is_cat=jnp.zeros((L,), bool).at[0].set(root_is_cat),
        best_bitset=jnp.zeros((L, W), jnp.uint32).at[0].set(root_bitset),
        done=jnp.asarray(False),
    )

    buckets = _bucket_sizes(N)

    def make_branch(S: int):
        """Bucket-S branch: partition leaf p's rows + smaller-child hist.

        Returns (order [N], n_left_local i32, hist_small [2, F, B]).
        """

        def branch(args):
            (order, start_p, count_p,
             bs_feature, bs_threshold, bs_default_left, bs_is_cat,
             bs_bitset, smaller_is_left, valid) = args
            # clamp the window so [pad_start, pad_start+S) stays in range
            pad_start = jnp.minimum(start_p, jnp.maximum(N - S, 0))
            offset = start_p - pad_start
            idx = jax.lax.dynamic_slice(order, (pad_start,), (S,))   # [S]
            pos = jnp.arange(S, dtype=jnp.int32)
            valid_row = (pos >= offset) & (pos < offset + count_p)

            col = X_t[bs_feature, idx].astype(jnp.int32)             # [S]
            mt = meta.missing_type[bs_feature]
            is_missing = ((mt == MISSING_ZERO)
                          & (col == meta.default_bin[bs_feature])) | \
                         ((mt == MISSING_NAN)
                          & (col == meta.num_bins[bs_feature] - 1))
            gl_num = jnp.where(is_missing, bs_default_left,
                               col <= bs_threshold)
            words = bs_bitset[jnp.clip(col >> 5, 0, W - 1)]
            gl_cat = ((words >> (col & 31).astype(jnp.uint32)) & 1) == 1
            go_left = jnp.where(bs_is_cat, gl_cat, gl_num) & valid_row

            # stable partition of the valid window: left rows first
            n_left = jnp.sum(go_left).astype(jnp.int32)
            go_right = valid_row & ~go_left
            pos_left = jnp.cumsum(go_left) - 1
            pos_right = n_left + jnp.cumsum(go_right) - 1
            new_pos = jnp.where(
                go_left, offset + pos_left,
                jnp.where(go_right, offset + pos_right, pos))
            new_slice = jnp.zeros((S,), jnp.int32).at[new_pos].set(idx)
            new_slice = jnp.where(valid, new_slice, idx)
            order = jax.lax.dynamic_update_slice(order, new_slice,
                                                 (pad_start,))

            # smaller-child histogram over this window (masked); global
            # smaller-ness is decided by the caller via left/right counts
            in_small = jnp.where(smaller_is_left, go_left, go_right)
            m = in_small.astype(jnp.float32) * in_bag[idx]
            Xg = jnp.take(X_t, idx, axis=1)                          # [F, S]
            vals = jnp.stack([grad[idx].astype(jnp.float32) * m,
                              hess[idx].astype(jnp.float32) * m], axis=0)
            hist_small = build_histogram(Xg, vals, B, cfg.rows_per_chunk,
                                         tiers=cfg.hist_tiers,
                                         impl=cfg.hist_impl)
            return order, n_left, hist_small

        return branch

    branches = [make_branch(S) for S in buckets]
    bucket_bounds = jnp.asarray(buckets, jnp.int32)

    def split_once(s, st: _FastState) -> _FastState:
        t = st.tree
        p = jnp.argmax(st.best.gain).astype(jnp.int32)
        bs = SplitResult(*[a[p] for a in st.best])
        bs_is_cat = st.best_is_cat[p]
        bs_bitset = st.best_bitset[p]
        valid = (bs.gain > 0.0) & ~st.done
        new_leaf = (s + 1).astype(jnp.int32)

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
        prev = st.leaf_parent_node[p]
        prev_i = jnp.maximum(prev, 0)
        fix = valid & (prev >= 0)
        t = t._replace(
            left_child=t.left_child.at[prev_i].set(
                jnp.where(fix & st.leaf_is_left[p], s, t.left_child[prev_i])),
            right_child=t.right_child.at[prev_i].set(
                jnp.where(fix & ~st.leaf_is_left[p], s,
                          t.right_child[prev_i])))

        # global smaller side (identical on all shards: counts are global,
        # coming from the psum-reduced histograms)
        smaller_is_left = bs.left_count <= bs.right_count

        # bucket by the shard-local leaf size; branches are collective-free
        # (the psum happens after the switch) so shards may diverge here
        start_p = st.leaf_start[p]
        count_p = st.leaf_count[p]
        bidx = jnp.searchsorted(bucket_bounds, count_p).astype(jnp.int32)
        bidx = jnp.minimum(bidx, len(buckets) - 1)

        order, n_left_local, hist_small_local = jax.lax.switch(
            bidx, branches,
            (st.order, start_p, count_p,
             bs.feature, bs.threshold, bs.default_left, bs_is_cat,
             bs_bitset, smaller_is_left, valid))
        hist_small = psum(hist_small_local)

        hist_parent = st.hist_cache[p]
        hist_large = hist_parent - hist_small
        hist_l = jnp.where(smaller_is_left, hist_small, hist_large)
        hist_r = jnp.where(smaller_is_left, hist_large, hist_small)

        # local partition bookkeeping: left child keeps slot [start_p,
        # start_p + n_left_local), right child gets the tail
        leaf_start = st.leaf_start.at[new_leaf].set(
            jnp.where(valid, start_p + n_left_local,
                      st.leaf_start[new_leaf]))
        leaf_count = st.leaf_count.at[p].set(
            jnp.where(valid, n_left_local, st.leaf_count[p]))
        leaf_count = leaf_count.at[new_leaf].set(
            jnp.where(valid, count_p - n_left_local,
                      leaf_count[new_leaf]))

        # per-leaf bookkeeping (identical to the baseline grower)
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

        hist_cache = st.hist_cache.at[p].set(
            jnp.where(valid, hist_l, st.hist_cache[p]))
        hist_cache = hist_cache.at[new_leaf].set(
            jnp.where(valid, hist_r, hist_cache[new_leaf]))

        # child split search: ONE vmapped call over both children, run
        # unconditionally (no lax.cond barrier; garbage results when ~valid
        # are discarded by the masked cache update below)
        can = depth_child < max_depth
        hist_lr = jnp.stack([hist_l, hist_r])
        sg_lr = jnp.stack([bs.left_sum_g, bs.right_sum_g])
        sh_lr = jnp.stack([bs.left_sum_h, bs.right_sum_h])
        c_lr = jnp.stack([bs.left_count, bs.right_count])
        o_lr = jnp.stack([bs.left_output, bs.right_output])
        s_lr, cat_lr, bits_lr = jax.vmap(search)(hist_lr, sg_lr, sh_lr,
                                                 c_lr, o_lr)
        s_lr = s_lr._replace(gain=jnp.where(can, s_lr.gain, NEG_INF))
        sl = SplitResult(*[a[0] for a in s_lr])
        sr = SplitResult(*[a[1] for a in s_lr])
        cl, cr = cat_lr[0], cat_lr[1]
        bl, br = bits_lr[0], bits_lr[1]
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

        return _FastState(
            tree=t, order=order,
            leaf_start=leaf_start, leaf_count=leaf_count,
            leaf_parent_node=leaf_parent_node, leaf_is_left=leaf_is_left,
            leaf_depth=leaf_depth, leaf_output=leaf_output,
            leaf_sum_g=leaf_sum_g, leaf_sum_h=leaf_sum_h,
            hist_cache=hist_cache,
            best=best, best_is_cat=best_is_cat, best_bitset=best_bitset,
            done=st.done | ~valid)

    if L > 1:
        state = jax.lax.fori_loop(0, L - 1, split_once, state)

    # reconstruct leaf_of_row ONCE from the final partition (leaf ranges
    # tile [0, N)): position j belongs to the leaf whose start is the
    # greatest <= j. Replaces a [N]-wide scatter per split.
    starts = jnp.where(state.leaf_count > 0, state.leaf_start, N + 1)
    ordr = jnp.argsort(starts)
    sorted_starts = starts[ordr]
    pos_leaf = ordr[jnp.clip(
        jnp.searchsorted(sorted_starts, jnp.arange(N), side="right") - 1,
        0, L - 1)].astype(jnp.int32)
    leaf_of_row = jnp.zeros((N,), jnp.int32).at[state.order].set(pos_leaf)
    return state.tree, leaf_of_row
