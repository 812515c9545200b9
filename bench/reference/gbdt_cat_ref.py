"""Plain reference for histogram gradient boosting on binary log-loss with
categorical columns, and the held-out AUC after every tree.

It imports nothing of the program under test. It is ``gbdt_ref.py``'s
follower (same objective, histograms, compensated float32 sums, count
synthesis and leaf outputs; its helpers are used as they are) extended
with what categorical columns add, as LightGBM publishes it:

* **binning**: a categorical column's value is its category; the
  category -> bin map is ingest's (as the numeric edges are: the binning
  sample is the program's draw), and ``map_numbers`` holds the map to
  the published rule against this file's own full-data counts: bin 0 is
  the other/missing bin, the mapped categories take bins 1..k once each,
  in descending count, and cover about 99 % of the rows.
* **decisions**: a categorical node sends a row left when its bin is in
  the node's left set (the dumped categories through the map); bin 0 is
  never in it. Every node's decision for a block of rows is one product
  of a 0/1 table over (feature, bin) with the block's one-hot bins.
* **the search** (``best_categorical``, FindBestThresholdCategoricalInner,
  feature_histogram.cpp): one-hot when ``num_bin <= max_cat_to_onehot``;
  else the categories with a count of at least ``cat_smooth`` sorted by
  G / (H + cat_smooth), prefixes from both ends up to
  ``min(max_cat_threshold, (used + 1) / 2)``, ``l2 + cat_l2``, and the
  ``min_data_per_group`` stepping rule.
* **leaf outputs**: a child of a sorted-mode categorical split takes
  ``-G / (H + l2 + cat_l2)``, as the reference's split record does.

Everything heavy is a matrix product over blocks of rows on whatever
device JAX has (the chip after the window, the CPU in tests).
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from reference.gbdt_ref import (_NO_LEAF, _finish_pass, _kahan_add,
                                _leaf_value_of_row, _round_up, _split_terms,
                                floor32)

_EPS = 1e-15


def bin_columns(X: np.ndarray, edges: Sequence[np.ndarray],
                cat_maps: Dict[int, Dict[int, int]]) -> np.ndarray:
    """[n, F] float32 rows -> [F, n] uint8 bins on the host. A numeric
    column's bin is the number of its bounds (floored to float32) under
    the value; a categorical one's is the map's, 0 where the category is
    not mapped (or NaN, or negative)."""
    n, F = X.shape
    out = np.empty((F, n), np.uint8)
    for f in range(F):
        col = X[:, f]
        if f in cat_maps:
            m = cat_maps[f]
            lut = np.zeros(max(m) + 2 if m else 1, np.uint8)
            for c, b in m.items():
                lut[c] = b
            iv = np.where(np.isfinite(col), col, -1).astype(np.int64)
            ok = (iv >= 0) & (iv < len(lut) - 1)
            out[f] = np.where(ok, lut[np.where(ok, iv, 0)], 0)
        else:
            bounds = floor32(np.asarray(edges[f], np.float64)[:-1])
            out[f] = np.searchsorted(bounds, col, side="left")
    return out


class CatTreeTables:
    """One drained tree as arrays: per internal node its feature and its
    go-left set over the feature's bins, the path matrix, and the
    program's own numbers."""

    def __init__(self, tree: Dict[str, Any], edges: Sequence[np.ndarray],
                 cat_maps: Dict[int, Dict[int, int]], n_bins: int,
                 n_leaves_pad: int):
        nodes: Dict[int, Dict[str, Any]] = {}
        lval, lcount, paths, parent_of = {}, {}, {}, {}
        stack = [(tree["tree_structure"], [])]
        while stack:
            node, path = stack.pop()
            if "split_index" not in node:
                li = int(node.get("leaf_index", 0))
                lval[li] = float(node["leaf_value"])
                lcount[li] = int(node.get("leaf_count", 0))
                paths[li] = path
                parent_of[li] = path[-1][0] if path else -1
                continue
            nodes[int(node["split_index"])] = node
            stack.append((node["left_child"], path + [(int(node[
                "split_index"]), 1)]))
            stack.append((node["right_child"], path + [(int(node[
                "split_index"]), -1)]))
        self.n_int, self.n_leaf = len(nodes), len(lval)
        L = J = n_leaves_pad
        if self.n_leaf > L:
            raise ValueError("tree has more leaves than the configuration")
        F, B = len(edges), n_bins
        self.feat = np.zeros(self.n_int, np.int64)
        self.is_cat = np.zeros(self.n_int, bool)
        self.thr_bin = np.zeros(self.n_int, np.int64)
        self.left = np.zeros((self.n_int, B), bool)   # go-left by bin
        self.prog_gain = np.zeros(self.n_int)
        self.prog_icount = np.zeros(self.n_int, np.int64)
        for j, node in nodes.items():
            f = self.feat[j] = int(node["split_feature"])
            self.prog_gain[j] = float(node["split_gain"])
            self.prog_icount[j] = int(node["internal_count"])
            if node["decision_type"] == "==":
                self.is_cat[j] = True
                m = cat_maps[f]
                for c in str(node["threshold"]).split("||"):
                    b = m.get(int(c))
                    if b is None:
                        raise ValueError(f"node {j}: category {c} has no "
                                         "bin in the map")
                    self.left[j, b] = True
            else:
                e = np.asarray(edges[f], np.float64)
                t = int(np.argmin(np.abs(e[:-1] - float(node["threshold"])))
                        ) if len(e) > 1 else 0
                self.thr_bin[j] = t
                self.left[j, :t + 1] = True
                nb = len(e)
                if node.get("missing_type") == "NaN":
                    self.left[j, nb - 1] = bool(node["default_left"])
                elif node.get("missing_type") == "Zero":
                    zb = int(np.sum(floor32(e[:-1]) < 0.0))
                    self.left[j, zb] = bool(node["default_left"])
        self.prog_lval = np.array([lval[i] for i in range(self.n_leaf)])
        self.prog_lcount = np.array([lcount[i] for i in range(self.n_leaf)])
        self.leaf_parent = np.array([parent_of[i]
                                     for i in range(self.n_leaf)])
        # the decision table [J, F*B]: node j's go-left bit of bin b of
        # its own feature, 0 elsewhere
        self.D = np.zeros((J, F * B), np.float32)
        for j in range(self.n_int):
            f = self.feat[j]
            self.D[j, f * B:(f + 1) * B] = self.left[j]
        self.P = np.zeros((L, J), np.float32)
        self.plen = np.full((L, 1), _NO_LEAF, np.float32)
        self.member = np.zeros((self.n_int, self.n_leaf), bool)
        for li, path in paths.items():
            self.plen[li, 0] = float(len(path))
            for j, sign in path:
                self.P[li, j] = float(sign)
                self.member[j, li] = True


def _onehot_bins(b, n_bins: int):
    """[F, R] uint8 -> [F*B, R] bfloat16 one-hot (exact)."""
    F, R = b.shape
    bi = b.astype(jnp.int32)
    return (bi[:, None, :] == jnp.arange(n_bins, dtype=jnp.int32)[
        None, :, None]).astype(jnp.bfloat16).reshape(F * n_bins, R)


def _mm(a, b, contract=((1,), (0,))):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("sub", "n_bins", "operand_dtype", "do_hist"),
    donate_argnames=("acc",))
def _tree_pass(bins, label, valid, score, prev_leaf, prev_vals, D, P, plen,
               acc, *, sub: int, n_bins: int, operand_dtype: str,
               do_hist: bool):
    """One tree over one uploaded block: gbdt_ref._tree_pass with each
    node's decision read from its (feature, bin) table."""
    n = bins.shape[1]
    L = P.shape[0]
    Db = D.astype(jnp.bfloat16)
    Pb = P.astype(jnp.bfloat16)

    def block(carry, i):
        acc = carry
        lo = i * sub
        b = jax.lax.dynamic_slice_in_dim(bins, lo, sub, axis=1)
        y = jax.lax.dynamic_slice_in_dim(label, lo, sub)
        m = jax.lax.dynamic_slice_in_dim(valid, lo, sub)
        s = jax.lax.dynamic_slice_in_dim(score, lo, sub)
        pl_ = jax.lax.dynamic_slice_in_dim(prev_leaf, lo, sub)
        s = s + _leaf_value_of_row(pl_.astype(jnp.int32), prev_vals)
        p = jax.nn.sigmoid(s)
        g = (p - y) * m
        h = p * (1.0 - p) * m
        loss = jnp.sum(m * (jnp.logaddexp(0.0, s) - y * s))
        Bm = _onehot_bins(b, n_bins)                           # [F*B, R]
        left = _mm(Db, Bm)                                     # [J, R] 0/1
        d = jnp.where(left > 0.5, 1.0, -1.0).astype(jnp.bfloat16)
        oh = _mm(Pb, d) == plen                                # [L, R]
        leaf_id = jnp.argmax(oh, axis=0).astype(jnp.uint8)
        ohm = oh & (m > 0)[None, :]
        terms = _split_terms(jnp.stack([g, h]), operand_dtype)
        vals = jnp.concatenate([t_[0:1] for t_ in terms]
                               + [t_[1:2] for t_ in terms], axis=0)
        C = vals.shape[0]
        A = (ohm[None, :, :].astype(jnp.bfloat16)
             * vals[:, None, :]).reshape(C * L, sub)
        sums = jnp.sum(A.astype(jnp.float32), axis=1)
        cnt = jnp.sum(ohm.astype(jnp.int32), axis=1)
        a_sum, a_sc, a_cnt, a_loss, a_lc, a_h, a_hc = acc
        a_sum, a_sc = _kahan_add(a_sum, a_sc, sums)
        a_loss, a_lc = _kahan_add(a_loss, a_lc, loss)
        a_cnt = a_cnt + cnt
        if do_hist:
            a_h, a_hc = _kahan_add(a_h, a_hc, _mm(A, Bm, ((1,), (1,))))
        return (a_sum, a_sc, a_cnt, a_loss, a_lc, a_h, a_hc), (s, leaf_id)

    acc, (s_new, leaf_new) = jax.lax.scan(block, acc, jnp.arange(n // sub))
    return s_new.reshape(n), leaf_new.reshape(n), acc


@functools.partial(jax.jit, static_argnames=("sub", "n_bins"))
def _leaf_pass(bins, score, prev_leaf, prev_vals, D, P, plen, *, sub: int,
               n_bins: int):
    """Held-out rows: move the scores by the previous tree's outputs and
    find each row's leaf in this tree."""
    n = bins.shape[1]
    Db = D.astype(jnp.bfloat16)
    Pb = P.astype(jnp.bfloat16)

    def block(_, i):
        lo = i * sub
        b = jax.lax.dynamic_slice_in_dim(bins, lo, sub, axis=1)
        s = jax.lax.dynamic_slice_in_dim(score, lo, sub)
        pl_ = jax.lax.dynamic_slice_in_dim(prev_leaf, lo, sub)
        s = s + _leaf_value_of_row(pl_.astype(jnp.int32), prev_vals)
        left = _mm(Db, _onehot_bins(b, n_bins))
        d = jnp.where(left > 0.5, 1.0, -1.0).astype(jnp.bfloat16)
        leaf = jnp.argmax(_mm(Pb, d) == plen, axis=0).astype(jnp.uint8)
        return 0, (s, leaf)

    _, (s_new, leaf_new) = jax.lax.scan(block, 0, jnp.arange(n // sub))
    return s_new.reshape(n), leaf_new.reshape(n)


def auc(scores: np.ndarray, y: np.ndarray) -> float:
    """Tie-aware area under the ROC curve (mean rank of the positives)."""
    s = np.asarray(scores, np.float64)
    order = np.argsort(s, kind="stable")
    ss = s[order]
    starts = np.flatnonzero(np.concatenate([[True], ss[1:] != ss[:-1]]))
    ends = np.concatenate([starts[1:], [len(ss)]])
    rank = np.empty(len(ss), np.float64)
    rank[order] = np.repeat((starts + ends + 1) / 2.0, ends - starts)
    pos = np.asarray(y) > 0
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    return float((rank[pos].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))


def best_categorical(g, h, cnt_factor, count, G, H, prm, num_bin):
    """FindBestThresholdCategoricalInner over one feature's histogram
    (g, h: [B] float64, bins 1..num_bin-1 the categories): the best gain
    over the parent (minus its own gain) and its left set of bins, or
    (-inf, None)."""
    l2 = float(prm.get("lambda_l2", 0.0))
    min_data = int(prm.get("min_data_in_leaf", 20))
    min_hess = float(prm.get("min_sum_hessian_in_leaf", 1e-3))
    smooth = float(prm.get("cat_smooth", 10.0))
    mdpg = int(prm.get("min_data_per_group", 100))
    parent = G * G / (H + l2)
    shift = parent + float(prm.get("min_gain_to_split", 0.0))
    cnt = np.rint(h * cnt_factor)
    best, best_set = -np.inf, None
    if num_bin <= int(prm.get("max_cat_to_onehot", 4)):
        for t in range(1, num_bin):
            lc, lh, lg = cnt[t], h[t], g[t]
            if lc < min_data or lh < min_hess:
                continue
            rc, rh = count - lc, H - lh - _EPS
            if rc < min_data or rh < min_hess:
                continue
            rg = G - lg
            gain = rg * rg / (rh + l2) + lg * lg / (lh + _EPS + l2)
            if gain > shift and gain > best:
                best, best_set = gain, [t]
        return best - parent, best_set
    idx = [t for t in range(1, num_bin) if cnt[t] >= smooth]
    ratio = {t: g[t] / (h[t] + smooth) for t in idx}
    idx.sort(key=lambda t: ratio[t])                 # stable, ascending
    used = len(idx)
    l2c = l2 + float(prm.get("cat_l2", 10.0))
    max_num_cat = min(int(prm.get("max_cat_threshold", 32)), (used + 1) // 2)
    for order in (idx, idx[::-1]):
        lg, lh, lc, group = 0.0, _EPS, 0.0, 0.0
        for i in range(min(used, max_num_cat)):
            t = order[i]
            lg += g[t]
            lh += h[t]
            lc += cnt[t]
            group += cnt[t]
            if lc < min_data or lh < min_hess:
                continue
            rc = count - lc
            if rc < min_data or rc < mdpg:
                break
            rh = H - lh
            if rh < min_hess:
                break
            if group < mdpg:
                continue
            group = 0.0
            rg = G - lg
            gain = lg * lg / (lh + l2c) + rg * rg / (rh + l2c)
            if gain <= shift:
                continue
            if gain > best:
                best, best_set = gain, list(order[:i + 1])
    return best - parent, best_set


def set_gain(g, h, left, G, H, prm, sorted_mode):
    """The gain over the parent of sending the bins in ``left`` left."""
    l2 = float(prm.get("lambda_l2", 0.0))
    l2c = l2 + float(prm.get("cat_l2", 10.0)) if sorted_mode else l2
    lg, lh = float(g[left].sum()), float(h[left].sum())
    rg, rh = G - lg, H - lh
    return lg * lg / (lh + l2c) + rg * rg / (rh + l2c) - G * G / (H + l2)


class CatFollower:
    """Holds the rows on the device, binned by the reference itself."""

    def __init__(self, edges: Sequence[np.ndarray],
                 cat_maps: Dict[int, Dict[int, int]], params: Dict[str, Any],
                 sub: int = 16384, upload_subs: int = 64):
        self.edges = [np.asarray(e, np.float64) for e in edges]
        self.cat_maps = {int(f): dict(m) for f, m in cat_maps.items()}
        self.n_feat = len(self.edges)
        self.num_bin = np.array([
            len(self.cat_maps[f]) + 1 if f in self.cat_maps
            else len(self.edges[f]) for f in range(self.n_feat)])
        self.n_bins = _round_up(int(self.num_bin.max()), 8)
        self.params = params
        self.sub = sub
        self.upb = sub * upload_subs
        self.L = _round_up(int(params["num_leaves"]), 8)
        self.blocks: List[Dict[str, Any]] = []
        self.rows = 0
        self.pos = 0.0
        self.bin_count = np.zeros((self.n_feat, self.n_bins), np.int64)
        self.cat_count: Dict[int, np.ndarray] = {}

    def load_rows(self, X: np.ndarray, y: np.ndarray, program_bins=None,
                  threads: int = 8) -> int:
        """Bin the rows on the host, count each column's rows by bin and
        each categorical column's rows by category, upload bins, label and
        validity block by block. ``program_bins(lo, hi)`` returns the
        program's device bins [F, hi-lo] of the same rows: the count of
        cells that differ is returned."""
        n, F = X.shape
        self.rows = n
        self.pos = float(np.sum(y > 0, dtype=np.float64))
        for f in self.cat_maps:
            iv = X[:, f]
            iv = iv[np.isfinite(iv) & (iv >= 0)].astype(np.int64)
            self.cat_count[f] = np.bincount(iv)
        starts = list(range(0, n, self.upb))
        with ThreadPoolExecutor(max_workers=threads) as pool:
            binned = pool.map(
                lambda lo: bin_columns(X[lo:lo + self.upb], self.edges,
                                       self.cat_maps), starts)
            mismatch = 0
            for lo, bins in zip(starts, binned):
                k = bins.shape[1]
                padn = _round_up(k, self.sub)
                yb = np.zeros(padn, np.float32)
                yb[:k] = y[lo:lo + k]
                valid = np.zeros(padn, np.float32)
                valid[:k] = 1.0
                bins = np.pad(bins, ((0, 0), (0, padn - k)))
                for f in range(F):
                    self.bin_count[f] += np.bincount(
                        bins[f, :k], minlength=self.n_bins)[:self.n_bins]
                bd = jnp.asarray(bins)
                if program_bins is not None:
                    mismatch += int(jnp.sum(
                        bd[:, :k] != program_bins(lo, lo + k)[:F]
                        .astype(jnp.uint8)))
                self.blocks.append({"bins": bd, "y": jnp.asarray(yb),
                                    "valid": jnp.asarray(valid), "k": k})
        return mismatch

    def map_numbers(self, max_bin: int) -> Dict[str, float]:
        """``cat_map_mismatch``: categorical columns whose map breaks the
        published rule as this file's full-data counts read it (bins
        1..k once each; every category over 1 % of the rows mapped; no
        mapped category under half the count of an unmapped one; the
        mapped ones cover at least 98.5 % of the rows: the rule cuts at
        99 % of ingest's sample, which may stray from the whole by a
        fraction of a per cent). ``bin_edges_bad`` and ``bin_mass_gap``
        as compare.edge_numbers, over the numeric columns."""
        bad = 0
        for f, m in self.cat_maps.items():
            cnt = self.cat_count[f]
            total = max(int(cnt.sum()), 1)
            bins = sorted(m.values())
            mapped = np.zeros(len(cnt), bool)
            for c in m:
                if 0 <= c < len(cnt):
                    mapped[c] = True
            share = cnt / total
            rare_mapped = share[mapped].min() if mapped.any() else 0.0
            common_unmapped = share[~mapped].max() if (~mapped).any() \
                else 0.0
            if (bins != list(range(1, len(bins) + 1))
                    or np.any(share[~mapped] > 0.01)
                    or rare_mapped * 2.0 < common_unmapped
                    or share[mapped].sum() < 0.985):
                bad += 1
        num = [f for f in range(self.n_feat) if f not in self.cat_maps]
        edges_bad = sum(
            1 for f in num
            if not 1 <= len(self.edges[f]) <= max_bin
            or np.any(np.diff(self.edges[f]) <= 0)
            or not np.isposinf(self.edges[f][-1]))
        heaviest = float(np.max(self.bin_count[num])) / max(self.rows, 1) \
            if num else 0.0
        return {"cat_map_mismatch": float(bad),
                "bin_edges_bad": float(edges_bad),
                "bin_mass_gap": heaviest * max_bin - 1.0}

    def follow(self, trees: Sequence[Dict[str, Any]], hist_trees: int,
               operand_dtype: str = "float32", held: "CatFollower" = None,
               held_y: np.ndarray = None) -> Dict[str, Any]:
        """Follow ``trees`` in order over the training rows (and, with
        ``held``, over its rows: their scores and AUC after every tree).
        Per tree: leaf sums, outputs and counts; for the first
        ``hist_trees`` the per-node histograms and the best split of
        every node."""
        prm = self.params
        lr = float(prm["learning_rate"])
        l2 = float(prm.get("lambda_l2", 0.0))
        l2c = l2 + float(prm.get("cat_l2", 10.0))
        onehot_max = int(prm.get("max_cat_to_onehot", 4))
        pavg = min(max(self.pos / self.rows, 1e-15), 1 - 1e-15)
        init = float(np.log(pavg / (1.0 - pavg)))
        L, sub, B = self.L, self.sub, self.n_bins
        nterm = 3 if operand_dtype == "float32" else 1
        C = 2 * nterm
        scores = [jnp.full(b["y"].shape, init, jnp.float32)
                  for b in self.blocks]
        prev_leaf = [jnp.zeros(b["y"].shape, jnp.uint8) for b in self.blocks]
        hblocks = held.blocks if held is not None else []
        hscores = [jnp.full(b["y"].shape, init, jnp.float32)
                   for b in hblocks]
        hleaf = [jnp.zeros(b["y"].shape, jnp.uint8) for b in hblocks]
        prev_vals = jnp.zeros((L,), jnp.float32)
        out_trees, aucs = [], []
        for t, tree in enumerate(trees):
            tt = CatTreeTables(tree, self.edges, self.cat_maps, B, L)
            do_hist = t < hist_trees
            hshape = (C * L, self.n_feat * B) if do_hist else (1, 1)
            acc = (jnp.zeros((C * L,), jnp.float32),
                   jnp.zeros((C * L,), jnp.float32),
                   jnp.zeros((L,), jnp.int32),
                   jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
                   jnp.zeros(hshape, jnp.float32),
                   jnp.zeros(hshape, jnp.float32))
            tabs = [jnp.asarray(a) for a in (tt.D, tt.P, tt.plen)]
            for bi, b in enumerate(self.blocks):
                scores[bi], prev_leaf[bi], acc = _tree_pass(
                    b["bins"], b["y"], b["valid"], scores[bi],
                    prev_leaf[bi], prev_vals, *tabs, acc, sub=sub,
                    n_bins=B, operand_dtype=operand_dtype, do_hist=do_hist)
            for bi, b in enumerate(hblocks):
                hscores[bi], hleaf[bi] = _leaf_pass(
                    b["bins"], hscores[bi], hleaf[bi], prev_vals, *tabs,
                    sub=sub, n_bins=B)
            sums = np.asarray(acc[0], np.float64).reshape(C, L)
            G = sums[:nterm].sum(axis=0)[:tt.n_leaf]
            H = sums[nterm:].sum(axis=0)[:tt.n_leaf]
            cnt = np.asarray(acc[2], np.int64)[:tt.n_leaf]
            # a child of a sorted-mode categorical split: l2 + cat_l2
            pj = tt.leaf_parent
            sorted_parent = np.array([
                p >= 0 and tt.is_cat[p]
                and self.num_bin[tt.feat[p]] > onehot_max for p in pj])
            l2_leaf = np.where(sorted_parent, l2c, l2)
            with np.errstate(divide="ignore", invalid="ignore"):
                step = np.where(cnt > 0, -G / (H + l2_leaf) * lr, 0.0)
            rec: Dict[str, Any] = {
                "tables": tt, "loss_before": float(acc[3]) / self.rows,
                "leaf_G": G, "leaf_H": H, "leaf_count": cnt,
                "leaf_step": step,
                "leaf_value": step + (init if t == 0 else 0.0),
                "bias": init if t == 0 else 0.0,
                "node_count": tt.member.astype(np.int64) @ cnt,
            }
            if do_hist:
                hist = np.asarray(acc[5], np.float64).reshape(
                    C, L, self.n_feat, B)
                hg = hist[:nterm].sum(axis=0)[:tt.n_leaf]
                hh = hist[nterm:].sum(axis=0)[:tt.n_leaf]
                rec.update(self._judge_splits(tt, hg, hh, cnt, prm))
            out_trees.append(rec)
            pv = np.zeros(L, np.float32)
            pv[:tt.n_leaf] = step
            prev_vals = jnp.asarray(pv)
            if hblocks:
                aucs.append(auc(self._finish_held(hblocks, hscores, hleaf,
                                                  prev_vals), held_y))
        loss = 0.0
        for bi, b in enumerate(self.blocks):
            scores[bi], l_ = _finish_pass(b["y"], b["valid"], scores[bi],
                                          prev_leaf[bi], prev_vals, sub=sub)
            loss += float(l_)
        out = {"trees": out_trees, "scores": scores, "init": init,
               "loss_after": loss / self.rows}
        if hblocks:
            out["held_score"] = self._finish_held(hblocks, hscores, hleaf,
                                                  prev_vals)
            out["auc"] = np.asarray(aucs)
        return out

    def _finish_held(self, hblocks, hscores, hleaf, prev_vals) -> np.ndarray:
        """The held-out scores after the tree whose leaves are ``hleaf``."""
        return np.concatenate([
            np.asarray(s + jax.lax.map(
                lambda a: _leaf_value_of_row(a.astype(jnp.int32), prev_vals),
                lf.reshape(-1, self.sub)).reshape(-1))[:b["k"]]
            for b, s, lf in zip(hblocks, hscores, hleaf)])

    def _judge_splits(self, tt: CatTreeTables, hg, hh, cnt, prm):
        """Per internal node its histograms and the reference's best split:
        numeric thresholds as gbdt_ref judges them, categorical features
        by best_categorical."""
        l2 = float(prm.get("lambda_l2", 0.0))
        min_data = int(prm.get("min_data_in_leaf", 20))
        min_hess = float(prm.get("min_sum_hessian_in_leaf", 1e-3))
        n_int = tt.n_int
        memb = tt.member.astype(np.float64)
        ng = np.tensordot(memb, hg, axes=(1, 0))        # [n_int, F, B]
        nh = np.tensordot(memb, hh, axes=(1, 0))
        ncnt = tt.member.astype(np.int64) @ cnt
        best = np.zeros(n_int)
        best_split: List[Any] = []
        for j in range(n_int):
            Gt, Ht = ng[j, 0].sum(), nh[j, 0].sum()
            cf = ncnt[j] / max(Ht, 1e-30)
            cand = (-np.inf, None)
            for f in range(self.n_feat):
                g, h, nb = ng[j, f], nh[j, f], int(self.num_bin[f])
                if f in self.cat_maps:
                    gain, left = best_categorical(g, h, cf, ncnt[j], Gt, Ht,
                                                  prm, nb)
                    if left is not None and gain > cand[0]:
                        cand = (gain, (f, "cat", sorted(left)))
                    continue
                gl, hl = np.cumsum(g), np.cumsum(h)
                gr, hr = Gt - gl, Ht - hl
                with np.errstate(divide="ignore", invalid="ignore"):
                    gain = gl * gl / (hl + l2) + gr * gr / (hr + l2) \
                        - Gt * Gt / (Ht + l2)
                cl = np.cumsum(np.rint(h * cf))
                cr = ncnt[j] - cl
                ok = (cl >= min_data) & (cr >= min_data) \
                    & (hl >= min_hess) & (hr >= min_hess)
                ok &= np.arange(len(g)) < nb - 1
                g2 = np.where(ok, gain, -np.inf)
                k = int(np.argmax(g2))
                if g2[k] > cand[0]:
                    cand = (float(g2[k]), (f, "num", k))
            best[j] = cand[0]
            best_split.append(cand[1])
        return {"node_g": ng, "node_h": nh, "gain_best": best,
                "best_split": best_split}

    def gain_of(self, rec, j: int, split) -> float:
        """The gain the reference gives ``split`` ((feature, "num", bin)
        or (feature, "cat", [bins])) at node j of a judged tree."""
        f, kind, at = split
        g, h = rec["node_g"][j, f], rec["node_h"][j, f]
        Gt, Ht = rec["node_g"][j, 0].sum(), rec["node_h"][j, 0].sum()
        if kind == "num":
            left = np.arange(len(g)) <= at
        else:
            left = np.zeros(len(g), bool)
            left[list(at)] = True
        sorted_mode = kind == "cat" and self.num_bin[f] > int(
            self.params.get("max_cat_to_onehot", 4))
        return set_gain(g, h, left, Gt, Ht, self.params, sorted_mode)
