"""Tree traversal on device: binned (training) and raw (serving).

Vectorized analog of Tree::GetLeaf / NumericalDecisionInner
(include/LightGBM/tree.h:358-440): all rows walk the tree in lockstep under a
`lax.while_loop`; each step gathers the current node's split feature column
and advances. `predict_leaf_binned` runs over binned features for
validation-score updates during training; `predict_margin_packed` runs the
same lockstep walk over RAW features and the concatenated packed-tree arrays
(models/predictor.py PackedModel.device_arrays) — the serving engine's
compiled scorer, jitted per padded batch bucket so arbitrary request sizes
hit a warm trace (serving/session.py)."""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.tree import (MISSING_NAN, MISSING_ZERO, _CATEGORICAL_MASK,
                           _DEFAULT_LEFT_MASK, _KZERO_THRESHOLD)
from .split import FeatureMeta


def predict_leaf_binned(
    split_feature: jnp.ndarray,   # [M] i32
    threshold_bin: jnp.ndarray,   # [M] i32
    default_left: jnp.ndarray,    # [M] bool
    left_child: jnp.ndarray,      # [M] i32 (negative = ~leaf)
    right_child: jnp.ndarray,     # [M] i32
    num_leaves: jnp.ndarray,      # i32 scalar
    X_t: jnp.ndarray,             # [F, N] binned feature-major
    meta: FeatureMeta,
    split_is_cat: jnp.ndarray = None,     # [M] bool (optional)
    split_cat_bitset: jnp.ndarray = None,  # [M, W] u32 (optional)
) -> jnp.ndarray:
    """Leaf index per row ([N] int32)."""
    if jax.default_backend() == "tpu" and path_walk_applies(X_t,
                                                            split_is_cat):
        # matrix products, not a gather a row and level (below)
        return predict_leaf_binned_paths(
            split_feature, threshold_bin, default_left, left_child,
            right_child, num_leaves, X_t, meta)
    N = X_t.shape[1]
    rows = jnp.arange(N, dtype=jnp.int32)

    # node >= 0: internal node to test; node < 0: arrived at leaf ~node
    node0 = jnp.where(num_leaves > 1,
                      jnp.zeros((N,), jnp.int32),
                      jnp.full((N,), -1, jnp.int32))

    def cond(node):
        return jnp.any(node >= 0)

    def body(node):
        nd = jnp.maximum(node, 0)
        f = split_feature[nd]                          # [N]
        bin_v = X_t[f, rows].astype(jnp.int32)         # [N] gather
        mt = meta.missing_type[f]
        is_missing = ((mt == MISSING_ZERO) & (bin_v == meta.default_bin[f])) \
            | ((mt == MISSING_NAN) & (bin_v == meta.num_bins[f] - 1))
        go_left = jnp.where(is_missing, default_left[nd],
                            bin_v <= threshold_bin[nd])
        if split_is_cat is not None:
            W = split_cat_bitset.shape[1]
            words = jnp.take_along_axis(
                split_cat_bitset[nd], jnp.clip(bin_v >> 5, 0, W - 1)[:, None],
                axis=1)[:, 0]
            go_left_cat = ((words >> (bin_v & 31).astype(jnp.uint32)) & 1) == 1
            go_left = jnp.where(split_is_cat[nd], go_left_cat, go_left)
        nxt = jnp.where(go_left, left_child[nd], right_child[nd])
        return jnp.where(node >= 0, nxt, node)

    node = jax.lax.while_loop(cond, body, node0)
    return ~node


_PATH_ROWS = 1 << 15     # rows a block of the path-matrix walk


def path_walk_applies(X_t, split_is_cat) -> bool:
    """Whether ``predict_leaf_binned_paths`` can stand in for the gather
    walk: bins that bfloat16 holds exactly (uint8) and no categorical
    split (a bitset lookup a node and row is a gather again)."""
    return split_is_cat is None and X_t.dtype == jnp.uint8


def tree_paths(left_child, right_child, num_leaves):
    """The tree as a path matrix: ``P`` [L, M] with +1 where leaf l's
    path leaves node j to the left, -1 to the right, 0 where j is not
    on it, and ``plen`` [L] the path's length (a large number for a leaf
    the tree does not have). Built from the child arrays with
    comparisons and small matmuls, no scatter: ``T`` links each node to
    its parent, and the sum of its powers times the signed links is
    doubled log2(M) times."""
    M = left_child.shape[0]
    L = M + 1
    live = jnp.arange(M, dtype=jnp.int32) < num_leaves - 1        # [M] nodes
    node = jnp.arange(M, dtype=jnp.int32)[:, None]
    leaf = -1 - jnp.arange(L, dtype=jnp.int32)[:, None]           # ~l
    lc = jnp.where(live, left_child, M + L)[None, :]
    rc = jnp.where(live, right_child, M + L)[None, :]
    f32 = jnp.float32

    def mm(a, b):       # entries are 0 and +-1 (one path a pair): exact
        return jax.lax.dot_general(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())), preferred_element_type=f32)

    # [child, parent]: +1 a left child, -1 a right child
    A = (lc == node).astype(f32) - (rc == node).astype(f32)       # [M, M]
    T = jnp.abs(A)
    steps = max(1, int(np.ceil(np.log2(max(M, 2)))))
    for _ in range(steps):
        A = A + mm(T, A)
        T = mm(T, T)
    Al = (lc == leaf).astype(f32) - (rc == leaf).astype(f32)      # [L, M]
    P = Al + mm(jnp.abs(Al), A)
    has_leaf = jnp.arange(L, dtype=jnp.int32) < jnp.maximum(num_leaves, 1)
    plen = jnp.where(has_leaf, jnp.sum(jnp.abs(P), axis=1), f32(1e6))
    return P, plen


def predict_leaf_binned_paths(split_feature, threshold_bin, default_left,
                              left_child, right_child, num_leaves, X_t,
                              meta: FeatureMeta) -> jnp.ndarray:
    """``predict_leaf_binned`` for numerical splits, as matrix products
    in place of a gather a row and level: every node's decision for
    every row of a block (the node's feature picked out of the block's
    bins by a one-hot product, exact for uint8 bins in bfloat16), then
    the leaf whose path agrees with all of them (``tree_paths``: the
    decisions +-1 times the path signs sum to the path's length
    exactly there and nowhere else). On the TPU a level of the gather
    walk over 4.5M rows costs what this whole walk does."""
    F, N = X_t.shape
    M = split_feature.shape[0]
    P, plen = tree_paths(left_child, right_child, num_leaves)
    featsel = (split_feature[:, None]
               == jnp.arange(F, dtype=jnp.int32)[None, :]) \
        .astype(jnp.bfloat16)                                      # [M, F]
    mt = meta.missing_type[split_feature][:, None]
    dbin = meta.default_bin[split_feature][:, None].astype(jnp.float32)
    nanbin = (meta.num_bins[split_feature] - 1)[:, None] \
        .astype(jnp.float32)
    thr = threshold_bin[:, None].astype(jnp.float32)
    dleft = default_left[:, None]
    Pb = P.astype(jnp.bfloat16)

    def block(xb):                                  # [F, R] uint8
        bin_v = jax.lax.dot_general(
            featsel, xb.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                    # [M, R]
        is_missing = ((mt == MISSING_ZERO) & (bin_v == dbin)) \
            | ((mt == MISSING_NAN) & (bin_v == nanbin))
        go_left = jnp.where(is_missing, dleft, bin_v <= thr)
        d = jnp.where(go_left, 1.0, -1.0).astype(jnp.bfloat16)
        agree = jax.lax.dot_general(
            Pb, d, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                    # [L, R]
        return jnp.argmax(agree == plen[:, None], axis=0) \
            .astype(jnp.int32)

    R = min(_PATH_ROWS, N)
    whole = N // R
    out = []
    if whole:
        out.append(jax.lax.map(
            lambda i: block(jax.lax.dynamic_slice_in_dim(X_t, i * R, R,
                                                         axis=1)),
            jnp.arange(whole, dtype=jnp.int32)).reshape(-1))
    if N - whole * R:
        out.append(block(X_t[:, whole * R:]))
    return out[0] if len(out) == 1 else jnp.concatenate(out)


class PackedDeviceArrays(NamedTuple):
    """Device-pinned packed multi-tree arrays (flat concatenation over all
    T trees, models/predictor.py PackedModel layout). `num_cat` is a
    static python int: models without categorical splits compile the
    bitset block out entirely."""
    node_start: jnp.ndarray       # [T] i32 node offset per tree
    leaf_start: jnp.ndarray       # [T] i32 leaf offset per tree
    split_feature: jnp.ndarray    # [M] i32
    threshold: jnp.ndarray        # [M] f32 (f32-floored f64 thresholds)
    threshold_in_bin: jnp.ndarray  # [M] i32 (categorical bitset index)
    decision_type: jnp.ndarray    # [M] i32
    left_child: jnp.ndarray       # [M] i32 (negative = ~leaf)
    right_child: jnp.ndarray      # [M] i32
    leaf_value: jnp.ndarray       # [L] f32
    single_leaf: jnp.ndarray      # [T] bool (stump trees start at leaf 0)
    cat_start: jnp.ndarray        # [T] i32 into cat_boundaries
    word_start: jnp.ndarray       # [T] i32 into cat_threshold words
    cat_boundaries: jnp.ndarray   # i32
    cat_threshold: jnp.ndarray    # u32 bitset words
    num_cat: int


def predict_margin_packed(pa: PackedDeviceArrays, X: jnp.ndarray,
                          K: int) -> jnp.ndarray:
    """[K, n] f32 margins for X [n, F] f32 raw features: every (row,
    tree) pair walks its tree in lockstep — one vectorized gather step
    per level under a `while_loop`, ~max-depth steps total (the device
    analog of PackedModel._leaves, and of the reference's single-row
    FastConfig walk, c_api.h:1399). Cost per row is O(T * depth) gathers
    vs the matmul predictor's O(T * L * M) flops, which is the right
    trade for serving-sized micro-batches. Numeric, missing and
    categorical splits; linear leaves stay on the host path."""
    n = X.shape[0]
    T = pa.node_start.shape[0]
    # node >= 0: LOCAL internal node to test; node < 0: arrived at ~leaf
    node0 = jnp.where(pa.single_leaf[None, :], -1, 0) \
        * jnp.ones((n, 1), jnp.int32)
    nan_x = jnp.isnan(X)

    def cond(node):
        return jnp.any(node >= 0)

    def body(node):
        g = jnp.maximum(node, 0) + pa.node_start[None, :]    # [n, T]
        f = pa.split_feature[g]
        fval = jnp.take_along_axis(X, f, axis=1)
        nan_mask = jnp.take_along_axis(nan_x, f, axis=1)
        dt = pa.decision_type[g]
        default_left = (dt & _DEFAULT_LEFT_MASK) != 0
        mt = (dt >> 2) & 3
        fval_n = jnp.where(nan_mask & (mt != MISSING_NAN), 0.0, fval)
        is_missing = ((mt == MISSING_ZERO)
                      & (jnp.abs(fval_n) <= _KZERO_THRESHOLD)) | \
                     ((mt == MISSING_NAN) & nan_mask)
        go_left = jnp.where(is_missing, default_left,
                            fval_n <= pa.threshold[g])
        if pa.num_cat > 0:
            is_cat = (dt & _CATEGORICAL_MASK) != 0
            valid = ~nan_mask & (fval >= 0)
            iv = jnp.where(valid, fval, 0).astype(jnp.int32)
            cb_idx = jnp.clip(
                pa.cat_start[None, :] + pa.threshold_in_bin[g], 0,
                jnp.maximum(pa.cat_boundaries.shape[0] - 2, 0))
            starts = pa.word_start[None, :] + pa.cat_boundaries[cb_idx]
            sizes = pa.cat_boundaries[cb_idx + 1] - pa.cat_boundaries[cb_idx]
            in_range = valid & (iv < sizes * 32)
            word = starts + jnp.minimum(iv >> 5, jnp.maximum(sizes - 1, 0))
            bits = pa.cat_threshold[
                jnp.clip(word, 0, pa.cat_threshold.shape[0] - 1)]
            gl_cat = in_range & (
                ((bits >> (iv & 31).astype(jnp.uint32)) & 1) == 1)
            go_left = jnp.where(is_cat, gl_cat, go_left)
        nxt = jnp.where(go_left, pa.left_child[g], pa.right_child[g])
        return jnp.where(node >= 0, nxt, node)

    node = jax.lax.while_loop(cond, body, node0)
    gl = pa.leaf_start[None, :] + ~node                      # [n, T]
    lv = pa.leaf_value[gl]
    return lv.reshape(n, T // K, K).sum(axis=1).T            # [K, n]


def add_tree_score(
    score: jnp.ndarray,           # [N] f32
    leaf_value: jnp.ndarray,      # [L] f32 (already shrunk)
    leaf_idx: jnp.ndarray,        # [N] i32
) -> jnp.ndarray:
    """ScoreUpdater::AddScore analog (src/boosting/score_updater.hpp:22)."""
    return score + leaf_value[leaf_idx]
