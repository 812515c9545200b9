"""Plain reference for histogram gradient boosting on binary log-loss.

It imports nothing of the program under test. It is the published
algorithm (LightGBM: binary objective with sigmoid 1, boost from the
average, per-feature bin histograms of gradient and hessian, split gain
G_l^2/(H_l+l2) + G_r^2/(H_r+l2) - G^2/(H+l2), leaf output -G/(H+l2) times
the learning rate, per-bin counts synthesized from hessians as
feature_histogram.hpp does) in straightforward jax.numpy and numpy, in
float32 with exact products and compensated sums.

What it is given: the raw float32 rows and labels the harness made from
the seed, the configuration's parameters, the bin edges that ingest
produced (they define the model's thresholds, as a vocabulary would;
what they are held to themselves is compare.edge_numbers, from the
per-bin row counts this file takes while it bins), and the trees the
timed path drained. It *follows* those trees: for every
tree it recomputes, from its own scores, the gradients, the rows of every
node (by its own binning of the raw rows and its own walk), the histogram
of every node, what the best split of every node is, and every leaf's
output; it then moves its own scores by its own leaf outputs. The tree
structure is the only thing it takes from the trees, the way a served
model's reference takes the served tokens: each split is judged by the
gap between the gain the reference gives it and the reference's best.

``operand_dtype`` is the precision of the per-row gradient and hessian as
they enter the histogram sums: "float32" is the reference; a lower one
("bfloat16", "float8_e4m3fn") makes the control, whose splits, leaf
outputs and scores then stand in the program's place.

Everything heavy is a matrix product over blocks of rows, so it runs on
whatever device JAX has (the chip after the window, the CPU in tests):
the leaf of a row is found with the path matrix of the tree (decisions
+-1 times path signs equals the path length exactly), and a histogram is
(leaf one-hot x value)^T (bin one-hot), with float32 values split into
three bfloat16 terms so that every product is exact and the sum is
float32, compensated (Kahan) across blocks.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

_NO_LEAF = 1.0e6     # path length of a padding leaf: never matched


def floor32(bounds: np.ndarray) -> np.ndarray:
    """Largest float32 <= each float64 bound: for a float32 value v,
    v <= b in float64 exactly when v <= floor32(b)."""
    b = np.asarray(bounds, np.float64)
    f = b.astype(np.float32)
    over = f.astype(np.float64) > b
    f[over] = np.nextafter(f[over], np.float32(-np.inf))
    return f


def pack_edges(edges: Sequence[np.ndarray]) -> np.ndarray:
    """[F, K] float32 inclusive upper bounds without each feature's last
    (+inf), padded with +inf: bin = number of bounds below the value."""
    k = max(len(e) - 1 for e in edges)
    out = np.full((len(edges), max(k, 1)), np.inf, np.float32)
    for f, e in enumerate(edges):
        out[f, :len(e) - 1] = floor32(np.asarray(e)[:-1])
    return out


class TreeTables:
    """One drained tree as arrays: split feature and threshold bin per
    internal node, the path matrix, and the program's own numbers."""

    def __init__(self, tree: Dict[str, Any], edges: Sequence[np.ndarray],
                 n_leaves_pad: int):
        feat, thr, gain, icount = {}, {}, {}, {}
        lval, lcount = {}, {}
        paths: Dict[int, List] = {}
        depth = 0

        stack = [(tree["tree_structure"], [])]
        while stack:
            node, path = stack.pop()
            if "split_index" not in node:
                li = int(node.get("leaf_index", 0))
                lval[li] = float(node["leaf_value"])
                lcount[li] = int(node.get("leaf_count", 0))
                paths[li] = path
                depth = max(depth, len(path))
                continue
            j = int(node["split_index"])
            feat[j] = int(node["split_feature"])
            thr[j] = float(node["threshold"])
            gain[j] = float(node["split_gain"])
            icount[j] = int(node["internal_count"])
            if node.get("decision_type", "<=") != "<=":
                raise ValueError("reference follows numeric splits only")
            stack.append((node["left_child"], path + [(j, 1)]))
            stack.append((node["right_child"], path + [(j, -1)]))

        self.n_int, self.n_leaf, self.depth = len(feat), len(lval), depth
        L = J = n_leaves_pad
        if self.n_leaf > L:
            raise ValueError("tree has more leaves than the configuration")
        F = len(edges)
        self.feat = np.array([feat[j] for j in range(self.n_int)], np.int64)
        self.thr = np.array([thr[j] for j in range(self.n_int)])
        self.prog_gain = np.array([gain[j] for j in range(self.n_int)])
        self.prog_icount = np.array([icount[j] for j in range(self.n_int)])
        self.prog_lval = np.array([lval[i] for i in range(self.n_leaf)])
        self.prog_lcount = np.array([lcount[i] for i in range(self.n_leaf)])
        # threshold value -> index of the bin bound it names
        self.thr_bin = np.zeros(self.n_int, np.int64)
        self.thr_off = np.zeros(self.n_int)
        for j in range(self.n_int):
            e = np.asarray(edges[self.feat[j]], np.float64)
            t = int(np.argmin(np.abs(e[:-1] - self.thr[j]))) if len(e) > 1 \
                else 0
            self.thr_bin[j] = t
            self.thr_off[j] = abs(e[t] - self.thr[j]) / max(abs(e[t]), 1e-30)
        self.featsel = np.zeros((J, _round_up(F, 32)), np.float32)
        self.thr_col = np.full((J, 1), 1.0e9, np.float32)
        self.P = np.zeros((L, J), np.float32)
        self.plen = np.full((L, 1), _NO_LEAF, np.float32)
        self.member = np.zeros((self.n_int, self.n_leaf), bool)
        self.left_member = np.zeros((self.n_int, self.n_leaf), bool)
        for j in range(self.n_int):
            self.featsel[j, self.feat[j]] = 1.0
            self.thr_col[j, 0] = float(self.thr_bin[j])
        for li, path in paths.items():
            self.plen[li, 0] = float(len(path))
            for j, sign in path:
                self.P[li, j] = float(sign)
                self.member[j, li] = True
                self.left_member[j, li] = sign > 0


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def round_to(v, dtype: str):
    """v (float32) rounded to ``dtype``'s exponent and mantissa bits, still
    held in float32. ``reduce_precision`` and not a cast there and back:
    the TPU compiler may elide such a pair of casts as excess precision,
    and a control rounded that way would read as the reference does."""
    fi = jnp.finfo(jnp.dtype(dtype))
    return jax.lax.reduce_precision(v, exponent_bits=int(fi.nexp),
                                    mantissa_bits=int(fi.nmant))


def _split_terms(v, operand_dtype: str):
    """[C, R] float32 -> list of bfloat16 [C, R] terms whose sum is v as
    the operand precision holds it."""
    if operand_dtype == "float32":
        hi = round_to(v, "bfloat16")
        r1 = v - hi
        mid = round_to(r1, "bfloat16")
        lo = round_to(r1 - mid, "bfloat16")
        return [t.astype(jnp.bfloat16) for t in (hi, mid, lo)]
    # a narrower float: round to it; the rounded value is exact in bf16
    return [round_to(v, operand_dtype).astype(jnp.bfloat16)]


def _kahan_add(s, c, x):
    y = x - c
    t = s + y
    return t, (t - s) - y


@functools.partial(jax.jit, static_argnames=("n_bounds",))
def _bin_block(x, edges, n_bounds: int):
    """x [R, F] float32 -> bins [F32, R] uint8 (feature-major, padded to
    32 features with zeros)."""
    xt = x.T

    def body(k, acc):
        e = jax.lax.dynamic_slice_in_dim(edges, k, 1, axis=1)   # [F, 1]
        return acc + (xt > e).astype(jnp.int32)

    b = jax.lax.fori_loop(0, n_bounds, body,
                          jnp.zeros(xt.shape, jnp.int32))
    F = xt.shape[0]
    return jnp.pad(b.astype(jnp.uint8), ((0, _round_up(F, 32) - F), (0, 0)))


@jax.jit
def _count_mismatch(a, b):
    return jnp.sum((a != b).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("n_bins",))
def _bin_counts(bins, valid, n_bins: int):
    """bins [F32, R] uint8, valid [R] -> [n_bins, F32] int32: the rows of
    each feature that fell into each bin."""
    m = valid > 0
    return jax.lax.map(
        lambda b: jnp.sum(((bins == b) & m[None, :]).astype(jnp.int32),
                          axis=1),
        jnp.arange(n_bins, dtype=jnp.uint8))


def _leaf_onehot(bins_blk, featsel, thr_col, P, plen):
    """bins_blk [F32, R] uint8 -> [L, R] bool: the leaf each row reaches."""
    cols = jax.lax.dot_general(
        featsel.astype(jnp.bfloat16), bins_blk.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # [J, R], exact
    d = jnp.where(cols <= thr_col, 1.0, -1.0).astype(jnp.bfloat16)
    s = jax.lax.dot_general(P.astype(jnp.bfloat16), d,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [L, R]
    return s == plen


def _leaf_value_of_row(leaf_id, leaf_vals):
    """leaf_id [R] int32, leaf_vals [L] f32 -> [R]: exact select-sum."""
    L = leaf_vals.shape[0]
    oh = leaf_id[None, :] == jnp.arange(L, dtype=jnp.int32)[:, None]
    return jnp.sum(jnp.where(oh, leaf_vals[:, None], 0.0), axis=0)


@functools.partial(
    jax.jit,
    static_argnames=("sub", "n_bins", "n_feat", "operand_dtype", "do_hist"),
    donate_argnames=("acc",))
def _tree_pass(bins, label, valid, score, prev_leaf, prev_vals,
               featsel, thr_col, P, plen, acc, *, sub: int, n_bins: int,
               n_feat: int, operand_dtype: str, do_hist: bool):
    """One tree over one uploaded block of rows.

    Moves ``score`` by the previous tree's leaf outputs, takes gradients,
    finds each row's leaf in this tree and accumulates per-leaf sums
    (and, with ``do_hist``, the per-leaf histograms) into ``acc``.
    Returns (new score, leaf id per row, acc)."""
    n = bins.shape[1]
    nsub = n // sub
    L = P.shape[0]

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
        # log-loss of the score before this tree
        loss = jnp.sum(m * (jnp.logaddexp(0.0, s) - y * s))
        oh = _leaf_onehot(b, featsel, thr_col, P, plen)         # [L, R]
        leaf_id = jnp.argmax(oh, axis=0).astype(jnp.uint8)
        ohm = oh & (m > 0)[None, :]
        terms = _split_terms(jnp.stack([g, h]), operand_dtype)
        # channel order: gradient terms, then hessian terms
        vals = jnp.concatenate([t_[0:1] for t_ in terms]
                               + [t_[1:2] for t_ in terms], axis=0)  # [C, R]
        C = vals.shape[0]
        A = (ohm[None, :, :].astype(jnp.bfloat16)
             * vals[:, None, :]).reshape(C * L, sub)
        sums = jnp.sum(A.astype(jnp.float32), axis=1)           # [C*L]
        cnt = jnp.sum(ohm.astype(jnp.int32), axis=1)            # [L]
        a_sum, a_sc, a_cnt, a_loss, a_lc, a_h, a_hc = acc
        a_sum, a_sc = _kahan_add(a_sum, a_sc, sums)
        a_loss, a_lc = _kahan_add(a_loss, a_lc, loss)
        a_cnt = a_cnt + cnt
        if do_hist:
            bi = b[:n_feat].astype(jnp.int32)
            Bm = (bi[:, None, :] == jnp.arange(
                n_bins, dtype=jnp.int32)[None, :, None]) \
                .astype(jnp.bfloat16).reshape(n_feat * n_bins, sub)
            part = jax.lax.dot_general(
                A, Bm, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)      # [C*L, F*B]
            a_h, a_hc = _kahan_add(a_h, a_hc, part)
        return (a_sum, a_sc, a_cnt, a_loss, a_lc, a_h, a_hc), (s, leaf_id)

    acc, (s_new, leaf_new) = jax.lax.scan(block, acc, jnp.arange(nsub))
    return s_new.reshape(n), leaf_new.reshape(n), acc


@functools.partial(jax.jit, static_argnames=("sub",))
def _finish_pass(label, valid, score, prev_leaf, prev_vals, *, sub: int):
    """Apply the last tree's outputs; return (score, log-loss sum)."""
    s = score + jax.lax.map(
        lambda a: _leaf_value_of_row(a.astype(jnp.int32), prev_vals),
        prev_leaf.reshape(-1, sub)).reshape(-1)
    loss = jnp.sum(valid * (jnp.logaddexp(0.0, s) - label * s))
    return s, loss


class Follower:
    """Holds the rows on the device, binned by the reference itself."""

    def __init__(self, edges: Sequence[np.ndarray], params: Dict[str, Any],
                 sub: int = 16384, upload_subs: int = 64):
        self.edges = [np.asarray(e, np.float64) for e in edges]
        self.n_feat = len(self.edges)
        self.n_bins = max(len(e) for e in self.edges)
        self.n_bins_pad = _round_up(self.n_bins, 8)
        self.params = params
        self.sub = sub
        self.upb = sub * upload_subs
        self.L = _round_up(int(params["num_leaves"]), 8)
        self.blocks: List[Dict[str, Any]] = []
        self.rows = 0
        self.pos = 0.0
        self.bin_count = np.zeros((self.n_feat, self.n_bins), np.int64)
        self._edges_dev = None

    # -- rows ---------------------------------------------------------
    def load_rows(self, X: np.ndarray, y: np.ndarray,
                  program_bins=None) -> int:
        """Upload the raw rows block by block, bin them, keep bins, label
        and validity on the device, and count each feature's rows bin by
        bin (``bin_count``). ``program_bins(lo, hi)`` returns the program's
        device bins [F?, hi-lo] for the same rows; the count of cells that
        differ is returned."""
        packed = pack_edges(self.edges)
        self._edges_dev = jnp.asarray(packed)
        n, F = X.shape
        self.rows = n
        self.pos = float(np.sum(y > 0, dtype=np.float64))
        mismatch = 0
        for lo in range(0, n, self.upb):
            hi = min(lo + self.upb, n)
            xb = X[lo:hi]
            yb = y[lo:hi].astype(np.float32)
            k = hi - lo
            if k < self.upb:          # pad the last block to whole sub-blocks
                padn = _round_up(k, self.sub)
                xb = np.concatenate(
                    [xb, np.zeros((padn - k, F), np.float32)])
                yb = np.concatenate([yb, np.zeros(padn - k, np.float32)])
            valid = np.zeros(len(yb), np.float32)
            valid[:k] = 1.0
            bins = _bin_block(jnp.asarray(xb), self._edges_dev,
                              n_bounds=packed.shape[1])
            valid_d = jnp.asarray(valid)
            self.bin_count += np.asarray(_bin_counts(
                bins, valid_d, n_bins=self.n_bins), np.int64).T[:F]
            if program_bins is not None:
                pb = program_bins(lo, hi)
                mismatch += int(_count_mismatch(
                    bins[:F, :k], pb[:F].astype(jnp.uint8)))
            self.blocks.append({"bins": bins, "y": jnp.asarray(yb),
                                "valid": valid_d, "k": k})
        return mismatch

    # -- follow -------------------------------------------------------
    def follow(self, trees: Sequence[Dict[str, Any]], hist_trees: int,
               operand_dtype: str = "float32") -> Dict[str, Any]:
        """Follow ``trees`` in order. Returns per-tree tables and numbers
        and the final scores (list of device arrays, one per block)."""
        prm = self.params
        lr = float(prm["learning_rate"])
        l2 = float(prm.get("lambda_l2", 0.0))
        pavg = min(max(self.pos / self.rows, 1e-15), 1 - 1e-15)
        init = float(np.log(pavg / (1.0 - pavg)))
        L, sub = self.L, self.sub
        nterm = 3 if operand_dtype == "float32" else 1
        C = 2 * nterm
        scores = [jnp.full(b["y"].shape, init, jnp.float32)
                  for b in self.blocks]
        prev_leaf = [jnp.zeros(b["y"].shape, jnp.uint8)
                     for b in self.blocks]
        prev_vals = jnp.zeros((L,), jnp.float32)
        out_trees = []
        for t, tree in enumerate(trees):
            tt = TreeTables(tree, self.edges, L)
            do_hist = t < hist_trees
            hshape = (C * L, self.n_feat * self.n_bins_pad) if do_hist \
                else (1, 1)
            acc = (jnp.zeros((C * L,), jnp.float32),
                   jnp.zeros((C * L,), jnp.float32),
                   jnp.zeros((L,), jnp.int32),
                   jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
                   jnp.zeros(hshape, jnp.float32),
                   jnp.zeros(hshape, jnp.float32))
            tabs = [jnp.asarray(a) for a in
                    (tt.featsel, tt.thr_col, tt.P, tt.plen)]
            for bi, b in enumerate(self.blocks):
                scores[bi], prev_leaf[bi], acc = _tree_pass(
                    b["bins"], b["y"], b["valid"], scores[bi],
                    prev_leaf[bi], prev_vals, *tabs, acc, sub=sub,
                    n_bins=self.n_bins_pad, n_feat=self.n_feat,
                    operand_dtype=operand_dtype, do_hist=do_hist)
            sums = np.asarray(acc[0], np.float64).reshape(C, L)
            G = sums[:nterm].sum(axis=0)[:tt.n_leaf]
            H = sums[nterm:].sum(axis=0)[:tt.n_leaf]
            cnt = np.asarray(acc[2], np.int64)[:tt.n_leaf]
            # a leaf no row reaches has no output of its own: 0
            with np.errstate(divide="ignore", invalid="ignore"):
                step = np.where(cnt > 0, -G / (H + l2) * lr, 0.0)
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
                    C, L, self.n_feat, self.n_bins_pad)
                hg = hist[:nterm].sum(axis=0)[:tt.n_leaf]
                hh = hist[nterm:].sum(axis=0)[:tt.n_leaf]
                rec.update(self._judge_splits(tt, hg, hh, cnt, prm))
            out_trees.append(rec)
            # padding leaves keep output 0; scores move by own outputs
            pv = np.zeros(L, np.float32)
            pv[:tt.n_leaf] = step
            prev_vals = jnp.asarray(pv)
        loss = 0.0
        for bi, b in enumerate(self.blocks):
            scores[bi], l_ = _finish_pass(b["y"], b["valid"], scores[bi],
                                          prev_leaf[bi], prev_vals, sub=sub)
            loss += float(l_)
        return {"trees": out_trees, "scores": scores, "init": init,
                "loss_after": loss / self.rows}

    def _judge_splits(self, tt: TreeTables, hg, hh, cnt, prm):
        """Per internal node: the gain the reference gives the program's
        split, the reference's best gain and where it lies."""
        l2 = float(prm.get("lambda_l2", 0.0))
        min_data = int(prm.get("min_data_in_leaf", 20))
        min_hess = float(prm.get("min_sum_hessian_in_leaf", 1e-3))
        n_int = tt.n_int
        chosen = np.zeros(n_int)
        best = np.zeros(n_int)
        best_at = np.zeros((n_int, 2), np.int64)
        tables = np.zeros((n_int,) + hg.shape[1:])
        memb = tt.member.astype(np.float64)
        ng = np.tensordot(memb, hg, axes=(1, 0))        # [n_int, F, B]
        nh = np.tensordot(memb, hh, axes=(1, 0))
        ncnt = tt.member.astype(np.int64) @ cnt
        nb = np.array([len(e) for e in self.edges])
        for j in range(n_int):
            Gt = ng[j, 0].sum()
            Ht = nh[j, 0].sum()
            parent = Gt * Gt / (Ht + l2)
            gl = np.cumsum(ng[j], axis=1)
            hl = np.cumsum(nh[j], axis=1)
            gr, hr = Gt - gl, Ht - hl
            with np.errstate(divide="ignore", invalid="ignore"):
                gain = gl * gl / (hl + l2) + gr * gr / (hr + l2) - parent
            f, tb = int(tt.feat[j]), int(tt.thr_bin[j])
            chosen[j] = gain[f, tb]
            tables[j] = gain
            # counts synthesized from hessians, as the published
            # algorithm does (RoundInt(hess * cnt_factor) per bin)
            cf = ncnt[j] / max(Ht, 1e-30)
            cl = np.cumsum(np.rint(nh[j] * cf), axis=1)
            cr = ncnt[j] - cl
            ok = (cl >= min_data) & (cr >= min_data) \
                & (hl >= min_hess) & (hr >= min_hess)
            ok &= np.arange(gain.shape[1])[None, :] < (nb[:, None] - 1)
            g2 = np.where(ok, gain, -np.inf)
            k = int(np.argmax(g2))
            best[j] = g2.flat[k]
            best_at[j] = divmod(k, gain.shape[1])
        return {"gain_chosen": chosen, "gain_best": best,
                "best_at": best_at, "gain_table": tables}
