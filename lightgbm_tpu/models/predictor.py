"""Packed multi-tree predictor: batch, single-row fast path, early stop.

The reference predicts by walking trees one at a time per row
(GBDT::PredictRaw, gbdt_prediction.cpp; Tree::Predict, tree.h:438) with
optional margin-based early stopping (prediction_early_stop.cpp) and a
single-row fast path that pre-resolves per-call state
(LGBM_BoosterPredictForMatSingleRowFastInit, c_api.h:1399-1428).

TPU-native re-design: all trees' node arrays are concatenated into flat
"packed" arrays once (the FastInit analog), then every (row, tree) pair
walks in lockstep — one vectorized step per tree level instead of a
Python loop per tree. The same packed arrays drive:

  * predict_margin:       [N, T]-lockstep chunked batch prediction
  * predict_single:       [T]-lockstep one-row fast path (~depth steps)
  * early stopping:       trees consumed in `freq`-sized groups; rows
                          whose margin clears the bound drop out of later
                          groups (binary: |margin|, multiclass: top-2 gap
                          — prediction_early_stop.cpp:14-58)
  * predict_margin_device: an MXU matmul formulation for accelerator
                          batch scoring (path-mismatch counting; see its
                          docstring) — numeric, missing and categorical
                          splits; linear leaves stay on the host paths
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..runtime.profiler import span
from .tree import (Tree, MISSING_NAN, MISSING_ZERO, _CATEGORICAL_MASK,
                   _DEFAULT_LEFT_MASK, _KZERO_THRESHOLD)


class PackedModel:
    """Flat concatenation of a [start_it, end_it) slice of the model's
    trees, iteration-major (tree t = iteration t // K, class t % K)."""

    def __init__(self, trees: List[Tree], num_class_models: int):
        self.K = num_class_models
        self.T = len(trees)
        node_counts = [max(t.num_leaves - 1, 1) for t in trees]
        leaf_counts = [t.num_leaves for t in trees]
        self.node_start = np.zeros(self.T + 1, np.int64)
        np.cumsum(node_counts, out=self.node_start[1:])
        self.leaf_start = np.zeros(self.T + 1, np.int64)
        np.cumsum(leaf_counts, out=self.leaf_start[1:])
        M = int(self.node_start[-1])
        L = int(self.leaf_start[-1])
        self.split_feature = np.zeros(M, np.int32)
        self.threshold = np.zeros(M, np.float64)
        self.threshold_in_bin = np.zeros(M, np.int32)
        self.decision_type = np.zeros(M, np.int8)
        self.left_child = np.zeros(M, np.int32)
        self.right_child = np.zeros(M, np.int32)
        self.leaf_value = np.zeros(L, np.float64)
        # categorical bitsets, concatenated with per-tree offsets
        self.num_cat = sum(t.num_cat for t in trees)
        cb = [np.zeros(0, np.int32)]
        ct = [np.zeros(0, np.uint32)]
        self.cat_start = np.zeros(self.T, np.int32)      # into boundaries
        self.word_start = np.zeros(self.T, np.int32)     # into bitset words
        cat_off = word_off = 0
        self.single_leaf = np.array(
            [t.num_leaves <= 1 for t in trees], bool)
        for i, t in enumerate(trees):
            a, b = self.node_start[i], self.node_start[i + 1]
            m = t.num_leaves - 1
            if m > 0:
                self.split_feature[a:a + m] = t.split_feature
                self.threshold[a:a + m] = t.threshold
                self.threshold_in_bin[a:a + m] = t.threshold_in_bin
                self.decision_type[a:a + m] = t.decision_type
                self.left_child[a:a + m] = t.left_child
                self.right_child[a:a + m] = t.right_child
            la = self.leaf_start[i]
            self.leaf_value[la:la + t.num_leaves] = t.leaf_value
            self.cat_start[i] = cat_off
            self.word_start[i] = word_off
            if t.num_cat > 0:
                cb.append(np.asarray(t.cat_boundaries, np.int32))
                ct.append(np.asarray(t.cat_threshold, np.uint32))
                cat_off += t.num_cat + 1
                word_off += len(t.cat_threshold)
        self.cat_boundaries = np.concatenate(cb)
        self.cat_threshold = np.concatenate(ct)
        # linear leaves (tree.cpp AddPredictionToScore linear path): a
        # uniform representation — non-linear trees get const=leaf_value
        # with zero coefficients, so one ragged pass covers mixed models
        self.has_linear = any(t.is_linear for t in trees)
        if self.has_linear:
            self.leaf_const = np.zeros(L, np.float64)
            counts = np.zeros(L, np.int32)
            feat_flat: List[int] = []
            coef_flat: List[float] = []
            for i, t in enumerate(trees):
                la = self.leaf_start[i]
                if t.is_linear:
                    self.leaf_const[la:la + t.num_leaves] = t.leaf_const
                    for li in range(t.num_leaves):
                        cs = t.leaf_coeff[li]
                        counts[la + li] = len(cs)
                        feat_flat.extend(t.leaf_features[li])
                        coef_flat.extend(cs)
                else:
                    self.leaf_const[la:la + t.num_leaves] = t.leaf_value
            self.coef_count = counts
            self.coef_start = np.zeros(L + 1, np.int64)
            np.cumsum(counts, out=self.coef_start[1:])
            self.coef_feat = np.asarray(feat_flat, np.int64)
            self.coef_val = np.asarray(coef_flat, np.float64)
            self.max_coeffs = int(counts.max()) if L else 0

    # ------------------------------------------------------------------
    def _step(self, X, rows, node, tsel):
        """One lockstep level: X [n, F]; rows [n] row ids; node [n, S]
        LOCAL node ids (>=0 active, <0 leaf); tsel [S] tree indices.
        Returns next node matrix."""
        active = node >= 0
        gnode = np.maximum(node, 0) + self.node_start[tsel][None, :]
        f = self.split_feature[gnode]
        fval = X[rows[:, None], f].astype(np.float64)
        dt = self.decision_type[gnode]
        default_left = (dt & _DEFAULT_LEFT_MASK) != 0
        missing_type = (dt.astype(np.int32) >> 2) & 3
        nan_mask = np.isnan(fval)
        fval_n = np.where(nan_mask & (missing_type != MISSING_NAN), 0.0,
                          fval)
        is_missing = ((missing_type == MISSING_ZERO)
                      & (np.abs(fval_n) <= _KZERO_THRESHOLD)) | \
                     ((missing_type == MISSING_NAN) & nan_mask)
        go_left = np.where(is_missing, default_left,
                           fval_n <= self.threshold[gnode])
        if self.num_cat > 0:
            is_cat = (dt & _CATEGORICAL_MASK) != 0
            if is_cat.any():
                go_left = np.where(is_cat,
                                   self._cat_go_left(fval, gnode, tsel),
                                   go_left)
        nxt = np.where(go_left, self.left_child[gnode],
                       self.right_child[gnode])
        return np.where(active, nxt, node)

    def _cat_go_left(self, fval, gnode, tsel):
        valid = ~np.isnan(fval) & (fval >= 0)
        iv = np.where(valid, fval, 0).astype(np.int64)
        cat_idx = self.threshold_in_bin[gnode].astype(np.int64)
        cb_idx = np.clip(self.cat_start[tsel][None, :] + cat_idx, 0,
                         max(len(self.cat_boundaries) - 2, 0))
        starts = self.word_start[tsel][None, :] + self.cat_boundaries[cb_idx]
        sizes = self.cat_boundaries[cb_idx + 1] - self.cat_boundaries[cb_idx]
        in_range = valid & (iv < sizes.astype(np.int64) * 32)
        word = starts + np.minimum(iv // 32, np.maximum(sizes - 1, 0))
        bits = self.cat_threshold[np.clip(word, 0,
                                          len(self.cat_threshold) - 1)]
        return in_range & (((bits >> (iv % 32).astype(np.uint32)) & 1) == 1)

    def _leaves(self, X, rows, tsel):
        """Leaf VALUE matrix [n, S] for the selected trees."""
        n = rows.shape[0]
        S = tsel.shape[0]
        node = np.where(self.single_leaf[tsel][None, :],
                        -1, 0).astype(np.int32) * np.ones((n, 1), np.int32)
        for _ in range(64 * 1024):
            if not (node >= 0).any():
                break
            node = self._step(X, rows, node, tsel)
        leaf = ~node
        gl = self.leaf_start[tsel][None, :] + leaf
        if not self.has_linear:
            return self.leaf_value[gl]
        # linear leaves: const + sum(coeff * raw); any NaN in a used
        # feature falls back to the constant leaf_value (tree.cpp:144-152)
        base = self.leaf_const[gl]
        add = np.zeros_like(base)
        nan_found = np.zeros(base.shape, bool)
        nc = self.coef_count[gl]
        for j in range(self.max_coeffs):
            m = j < nc
            idx = np.clip(self.coef_start[gl] + j, 0,
                          max(len(self.coef_feat) - 1, 0))
            f = self.coef_feat[idx] if len(self.coef_feat) else idx
            v = X[rows[:, None], f].astype(np.float64)
            nan_found |= m & np.isnan(v)
            add += np.where(m, np.nan_to_num(v) * self.coef_val[idx], 0.0)
        return np.where(nan_found, self.leaf_value[gl], base + add)

    # ------------------------------------------------------------------
    def predict_margin(
        self,
        X: np.ndarray,                      # [N, F] raw features
        early_stop_margin: Optional[float] = None,
        early_stop_freq: int = 10,
        chunk: int = 8192,
    ) -> np.ndarray:
        """[K, N] f64 margins. With `early_stop_margin`, trees are
        consumed in freq-iteration groups and rows whose margin clears
        the bound stop evaluating further trees
        (prediction_early_stop.cpp: binary |margin| > m at :30,
        multiclass top1-top2 > m at :14)."""
        N = X.shape[0]
        K = self.K
        n_iters = self.T // K
        out = np.zeros((K, N), np.float64)
        for c0 in range(0, N, chunk):
            rows = np.arange(c0, min(c0 + chunk, N))
            if early_stop_margin is None:
                tsel = np.arange(self.T)
                lv = self._leaves(X, rows, tsel)          # [n, T]
                out[:, rows] = lv.reshape(len(rows), n_iters, K) \
                    .sum(axis=1).T
            else:
                alive = rows
                acc = np.zeros((K, len(rows)), np.float64)
                for g0 in range(0, n_iters, early_stop_freq):
                    g1 = min(g0 + early_stop_freq, n_iters)
                    tsel = np.arange(g0 * K, g1 * K)
                    lv = self._leaves(X, alive, tsel)
                    local = np.searchsorted(rows, alive)
                    acc[:, local] += lv.reshape(len(alive), g1 - g0, K) \
                        .sum(axis=1).T
                    if g1 >= n_iters:
                        break
                    m = acc[:, local]
                    if K == 1:
                        go_on = np.abs(m[0]) < early_stop_margin
                    else:
                        s = np.sort(m, axis=0)
                        go_on = (s[-1] - s[-2]) < early_stop_margin
                    alive = alive[go_on]
                    if alive.size == 0:
                        break
                out[:, rows] = acc
        return out

    # ------------------------------------------------------------------
    def device_arrays(self):
        """Pinned device copies of the packed arrays for the serving
        engine's jitted lockstep walk (ops/predict.py
        predict_margin_packed): uploaded ONCE per model version and
        reused by every compiled bucket trace — the device analog of the
        host ``_packed_model`` cache. Thresholds are f32-floored
        (``floor_threshold_f32``) so the device's single-precision
        compare routes f32 feature values exactly like the host's
        double-precision walk."""
        cached = getattr(self, "_device_arrays", None)
        if cached is not None:
            return cached
        if self.has_linear:
            raise ValueError("device serving path does not support "
                             "linear leaves; use the host path")
        import jax.numpy as jnp
        from ..ops.predict import PackedDeviceArrays
        pa = PackedDeviceArrays(
            node_start=jnp.asarray(self.node_start[:-1], jnp.int32),
            leaf_start=jnp.asarray(self.leaf_start[:-1], jnp.int32),
            split_feature=jnp.asarray(self.split_feature, jnp.int32),
            threshold=jnp.asarray(
                floor_threshold_f32(self.threshold), jnp.float32),
            threshold_in_bin=jnp.asarray(self.threshold_in_bin, jnp.int32),
            decision_type=jnp.asarray(self.decision_type, jnp.int32),
            left_child=jnp.asarray(self.left_child, jnp.int32),
            right_child=jnp.asarray(self.right_child, jnp.int32),
            leaf_value=jnp.asarray(self.leaf_value, jnp.float32),
            single_leaf=jnp.asarray(self.single_leaf),
            cat_start=jnp.asarray(self.cat_start, jnp.int32),
            word_start=jnp.asarray(self.word_start, jnp.int32),
            cat_boundaries=jnp.asarray(self.cat_boundaries, jnp.int32),
            cat_threshold=jnp.asarray(self.cat_threshold, jnp.uint32),
            num_cat=int(self.num_cat),
        )
        self._device_arrays = pa
        return pa

    # ------------------------------------------------------------------
    def predict_single(self, x: np.ndarray) -> np.ndarray:
        """[K] margins for ONE row — all trees walk in lockstep, ~depth
        vectorized [T]-sized steps (the FastConfig single-row analog:
        the packed arrays are the pre-resolved state)."""
        X = x.reshape(1, -1)
        rows = np.zeros(1, np.int64)
        lv = self._leaves(X, rows, np.arange(self.T))[0]  # [T]
        return lv.reshape(self.T // self.K, self.K).sum(axis=0)


def linear_tree_indices(trees) -> List[int]:
    """Indices of linear-leaf trees. The paths that must refuse them —
    the C++ if-else codegen (basic.py dump_model_to_cpp), the stablehlo
    AOT exporter (export/compile.py), TreeSHAP (models/shap.py) — all
    name the offending trees in their error, so the fix (retrain with
    linear_tree=false, or drop the trees) is obvious from the message."""
    return [i for i, t in enumerate(trees)
            if getattr(t, "is_linear", False)]


def format_tree_indices(linear: List[int]) -> str:
    """'tree(s) [0, 3, 7]' (first 8, elided beyond) — the shared error
    phrasing for linear-tree refusals."""
    return (f"tree(s) {linear[:8]}"
            f"{'...' if len(linear) > 8 else ''}")


def floor_threshold_f32(t64: np.ndarray) -> np.ndarray:
    """The f64 thresholds floored to the largest f32 <= each: for f32
    feature values v, (v <= thr_f64) == (v <= thr_f32floor), so a device
    single-precision compare routes boundary rows exactly like the
    host's double-precision walk."""
    t64 = np.asarray(t64, np.float64)
    t32 = t64.astype(np.float32)
    over = t32.astype(np.float64) > t64
    t32[over] = np.nextafter(t32[over], np.float32(-np.inf))
    return t32


def _tree_path_tables(tree, M_pad, L_pad, W):
    """Per-tree path tables for the matmul predictor: P [L_pad, M_pad]
    (+1 where leaf l's path goes RIGHT at node m, -1 where LEFT, 0 off
    path), c [L_pad] = number of LEFT edges on the path, so
    mismatches(l, r) = c[l] + sum_m P[l, m] * go_left[m, r] equals zero
    exactly at the row's leaf. Also packs per-node split metadata."""
    n, m = tree.num_leaves, max(tree.num_leaves - 1, 0)
    P = np.zeros((L_pad, M_pad), np.float32)
    c = np.zeros(L_pad, np.float32)
    stack = [(0, [])] if m > 0 else []
    while stack:
        node, path = stack.pop()
        for child, is_left in ((int(tree.left_child[node]), True),
                               (int(tree.right_child[node]), False)):
            p2 = path + [(node, is_left)]
            if child < 0:
                for nd, il in p2:
                    # go_left=1 on a LEFT edge is a match: P=-1, c+=1
                    P[~child, nd] = -1.0 if il else 1.0
                    c[~child] += 1.0 if il else 0.0
            else:
                stack.append((child, p2))
    # unreached padding leaves must never win the ==0 test
    c[n:] = 1e9
    if n == 1:
        c[0] = 0.0          # stump: single leaf always matches
    feat = np.zeros(M_pad, np.int32)
    thr = np.zeros(M_pad, np.float32)
    dt = np.zeros(M_pad, np.int8)
    bits = np.zeros((M_pad, W), np.uint32)
    lv = np.zeros(L_pad, np.float32)
    lv[:n] = tree.leaf_value
    if m > 0:
        feat[:m] = tree.split_feature
        thr[:m] = floor_threshold_f32(tree.threshold)
        dt[:m] = tree.decision_type
        for i in range(m):
            if dt[i] & _CATEGORICAL_MASK:
                ci = int(tree.threshold_in_bin[i])
                a, b = tree.cat_boundaries[ci], tree.cat_boundaries[ci + 1]
                words = tree.cat_threshold[a:b][:W]
                bits[i, :len(words)] = words
    return P, c, feat, thr, dt, bits, lv


def build_device_tables(trees, num_class_models: int, F: int):
    """Upload per-tree path tables for predict_margin_device (cacheable
    across calls while the model is unchanged — a serving loop should
    reuse them like the host _packed_model cache)."""
    if any(getattr(t, "is_linear", False) for t in trees):
        raise ValueError("predict_margin_device does not support linear "
                         "leaves; use predict_margin")
    import jax.numpy as jnp

    M_pad = max(max((t.num_leaves - 1 for t in trees), default=1), 1)
    M_pad = int(np.ceil(M_pad / 8) * 8)
    L_pad = int(np.ceil(max(t.num_leaves for t in trees) / 8) * 8)
    if any(t.num_cat > 0 for t in trees):
        W = max(int(np.diff(t.cat_boundaries).max()) for t in trees
                if t.num_cat > 0)
    else:
        W = 0          # the categorical block compiles out entirely
    tabs = [_tree_path_tables(t, M_pad, L_pad, W) for t in trees]
    P = jnp.asarray(np.stack([a[0] for a in tabs]))       # [T, L, M]
    c = jnp.asarray(np.stack([a[1] for a in tabs]))       # [T, L]
    feat = np.stack([a[2] for a in tabs])                  # [T, M]
    thr = jnp.asarray(np.stack([a[3] for a in tabs]))
    dt = jnp.asarray(np.stack([a[4] for a in tabs]).astype(np.int32))
    bits = jnp.asarray(np.stack([a[5] for a in tabs]))     # [T, M, W]
    lv = jnp.asarray(np.stack([a[6] for a in tabs]))       # [T, L]
    # exact one-hot feature selector (bf16 one-hots are exact; HIGHEST
    # keeps the f32 values un-rounded through the MXU)
    ohf = jnp.asarray((feat[:, :, None]
                       == np.arange(F)[None, None, :]).astype(np.float32))
    return (ohf, thr, dt, bits, P, c, lv, num_class_models)


def device_tables_bytes(trees, num_features: int) -> int:
    """Approximate device memory of build_device_tables' arrays (ohf
    [T, M_pad, F] + P [T, L_pad, M_pad], both f32) — kept NEXT to the
    builder so routing budgets track the layout."""
    Mp = max(max((t.num_leaves - 1 for t in trees), default=1), 1)
    Mp = int(np.ceil(Mp / 8) * 8)
    Lp = int(np.ceil(max(t.num_leaves for t in trees) / 8) * 8)
    return len(trees) * (Mp * num_features + Lp * Mp) * 4


def predict_margin_device(trees, num_class_models: int, X,
                          chunk: int = 65536, tables=None) -> "object":
    """Device batch margins — the TPU-native matmul formulation (no
    gathers, no per-row walks; CUDA analog: gbdt_prediction kernels over
    CUDATree, cuda_tree.hpp:29, rebuilt for the MXU):

      1. per tree, node decisions for ALL rows at once: feature values
         arrive via an exact one-hot contraction oh_feat @ X_chunk
         ([M, F] @ [F, n]), then missing/categorical logic elementwise;
      2. each row's leaf is the unique leaf whose path constraints all
         hold: mismatch counts for ALL (leaf, row) pairs are ONE matmul
         P @ go_left + c, and the leaf value lands via a second exact
         one-hot contraction over (count == 0).

    X is [N, F] float32 (device or host); returns [K, N] f32 margins.
    Linear leaves are not supported (use the host path)."""
    import jax
    import jax.numpy as jnp

    if tables is None:
        with span("predict/tables"):
            tables = build_device_tables(trees, num_class_models,
                                         X.shape[1])
    ohf, thr, dt, bits, P, c, lv, K = tables
    F = X.shape[1]
    N = X.shape[0]
    with span("predict/upload", bytes_up=N * F * 4):
        Xd = jnp.asarray(np.asarray(X, np.float32)) \
            if not isinstance(X, jnp.ndarray) else X.astype(jnp.float32)
    with span("predict/layout"):
        Np = int(np.ceil(N / chunk) * chunk)
        Xt = jnp.pad(Xd, ((0, Np - N), (0, 0))).T.reshape(
            F, Np // chunk, chunk)
    with span("predict/dispatch"):
        out_dev = _get_device_margin()(Xt, ohf, thr, dt, bits, P, c, lv,
                                       K=K)
    # the wait device_get would make anyway, timed apart from the copy
    with span("predict/wait_device"):
        out_dev.block_until_ready()
    with span("predict/download", bytes_down=out_dev.nbytes):
        out = np.asarray(jax.device_get(out_dev))
    with span("predict/cast_out"):
        return out[:, :N].astype(np.float64)


_DEVICE_MARGIN_JIT = None


def _get_device_margin():
    """Module-level jit cache (jax imported lazily — this module must
    stay importable host-only)."""
    global _DEVICE_MARGIN_JIT
    if _DEVICE_MARGIN_JIT is None:
        import jax
        _DEVICE_MARGIN_JIT = jax.jit(_device_margin,
                                     static_argnames=("K",))
    return _DEVICE_MARGIN_JIT


def _device_margin(Xt, ohf, thr, dt, bits, P, c, lv, *, K):
    """[K, N] margins on device; Xt [F, n_chunks, chunk] f32. Jitted at
    module level so repeated predict calls with same-shaped models and
    chunks reuse the compilation."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    W = int(bits.shape[2])

    def run_chunk(Xc_t):                                   # [F, n]
        nan_f = jnp.isnan(Xc_t)
        Xclean = jnp.where(nan_f, 0.0, Xc_t)
        nan_f32 = nan_f.astype(jnp.float32)

        def per_tree(carry, tab):
            ohf_t, thr_t, dt_t, bits_t, P_t, c_t, lv_t = tab
            with jax.named_scope("predict/feature_select"):
                fval = jax.lax.dot_general(
                    ohf_t, Xclean, (((1,), (0,)), ((), ())),
                    precision=hp)                          # [M, n]
                nan_mask = jax.lax.dot_general(
                    ohf_t, nan_f32, (((1,), (0,)), ((), ())),
                    precision=hp) > 0.5
            with jax.named_scope("predict/path_match"):
                mt = (dt_t[:, None] >> 2) & 3
                fval_n = jnp.where(nan_mask, 0.0, fval)
                is_missing = ((mt == MISSING_ZERO)
                              & (jnp.abs(fval_n) <= _KZERO_THRESHOLD)) | \
                             ((mt == MISSING_NAN) & nan_mask)
                default_left = (dt_t[:, None] & _DEFAULT_LEFT_MASK) != 0
                go_left = jnp.where(is_missing, default_left,
                                    fval_n <= thr_t[:, None])
                is_cat = (dt_t[:, None] & _CATEGORICAL_MASK) != 0
                if W > 0:
                    valid = ~nan_mask & (fval >= 0)
                    iv = jnp.where(valid, fval, 0).astype(jnp.int32)
                    widx = jnp.clip(iv >> 5, 0, W - 1)
                    wsel = jnp.zeros(iv.shape, jnp.uint32)
                    for w in range(W):
                        wsel = jnp.where(widx == w, bits_t[:, w:w + 1], wsel)
                    in_range = valid & (iv < W * 32)
                    gl_cat = in_range & (
                        ((wsel >> (iv & 31).astype(jnp.uint32)) & 1) == 1)
                    go_left = jnp.where(is_cat, gl_cat, go_left)
                # mismatch count per (leaf, row): ONE matmul. Products are
                # 0/+-1 -> exact in bf16 with f32 accumulation.
                counts = jax.lax.dot_general(
                    P_t, go_left.astype(jnp.float32),
                    (((1,), (0,)), ((), ())), precision=hp) + c_t[:, None]
                hit = (counts == 0).astype(jnp.float32)        # [L, n]
            with jax.named_scope("predict/leaf_sum"):
                out = jax.lax.dot_general(
                    lv_t[None, :], hit, (((1,), (0,)), ((), ())),
                    precision=hp)[0]                       # [n]
                return carry + out.astype(jnp.float32), None

        n = Xc_t.shape[1]
        outs = []
        for k in range(K):
            tab_k = (ohf[k::K], thr[k::K], dt[k::K], bits[k::K],
                     P[k::K], c[k::K], lv[k::K])
            acc, _ = jax.lax.scan(per_tree, jnp.zeros((n,), jnp.float32),
                                  tab_k)
            outs.append(acc)
        return jnp.stack(outs)                             # [K, n]

    def step(_, Xc_t):
        return None, run_chunk(Xc_t)

    _, outs = jax.lax.scan(step, None, jnp.moveaxis(Xt, 1, 0))
    return jnp.moveaxis(outs, 0, 1).reshape(outs.shape[1], -1)   # [K, Np]
