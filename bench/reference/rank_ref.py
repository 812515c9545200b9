"""Plain reference for LambdaRank boosting and NDCG.

It imports nothing of the program under test. It is the published
algorithm (LightGBM: rank_objective.hpp LambdarankNDCG, sigmoid 1,
truncation level 30, lambdarank_norm, label gain 2^l - 1;
dcg_calculator.cpp for DCG and NDCG@k; the tree follower's sums and
split gains as gbdt_ref.py, whose helpers it takes, fed with these
gradients instead of the binary objective's) in straightforward
jax.numpy, float32, matmul precision "highest".

**Its own grouping.** The program gathers queries into dense buckets by
padded length and sorts each bucket. The reference never pads: rows stay
flat, a query is a run of neighbouring rows, and everything per query is
one global stable sort by (query, -score) and segment sums. The sort is
NumPy's ``lexsort`` on the host (ranges of whole queries on a few
threads): a flat sort of 13.6M rows under two keys takes the TPU's
compiler over a minute to build, and a reference that compiles for five
minutes is no use beside a window of 20 s. Everything after the order is
on the device:

* a row's rank is its place in the sorted order less its query's start;
* the pairs LightGBM visits are (i, j) with i < min(T, len - 1), j > i,
  labels unlike: seen from row j they are "j against each of the top
  min(rank_j, T) rows of its query", a [rows, T] block, walked in blocks
  of rows; row j's own lambda is the block's row sum, the top rows'
  lambdas are the block's segment sums by query ([queries, T]);
* NDCG@k is a segment sum of gain x discount over the rows of rank < k.

Departures from the published description, each deliberate:
  - pairs are evaluated in float32 (LightGBM: double), as the program;
  - the sigmoid is evaluated, not read from LightGBM's 1M-entry table;
  - a pair's sums are accumulated by XLA's segment sum (order free),
    not in LightGBM's row order;
  - the stable sort breaks score ties by row order (LightGBM's
    std::stable_sort does the same; at tree 1 every score is equal);
  - the order is taken on the host (above); -0.0 and 0.0 tie there as
    they do in the program's sort.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from reference import gbdt_ref
from reference.gbdt_ref import (TreeTables, _kahan_add, _leaf_onehot,
                                _leaf_value_of_row, _round_up, _split_terms)

_KEPS = 1e-15


def label_gain(max_label: int = 31) -> np.ndarray:
    return (2.0 ** np.arange(max_label + 1) - 1.0).astype(np.float32)


def host_order(qid: np.ndarray, start: np.ndarray, key: np.ndarray,
               threads: int = 8) -> np.ndarray:
    """Stable order of the flat rows by (query, key), as int32 places:
    ``np.lexsort`` over ranges of whole queries, one range a thread."""
    n, nq = len(qid), len(start)
    cuts = [0] + [int(start[(nq * k) // threads])
                  for k in range(1, threads)] + [n]
    out = np.empty(n, np.int32)

    def work(k: int) -> None:
        lo, hi = cuts[k], cuts[k + 1]
        if hi > lo:
            out[lo:hi] = lo + np.lexsort((key[lo:hi], qid[lo:hi]))

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(work, range(threads)))
    return out


def _ranked(qid, start, src, payload):
    """``src``: the stable order of the rows by (query, key). Returns the
    payloads in that order and each sorted row's rank within its query
    (rows move within their query only, so the sorted rows' queries are
    ``qid`` itself)."""
    n = qid.shape[0]
    rank = jnp.arange(n, dtype=jnp.int32) - start[qid]
    return tuple(a[src] for a in payload), rank


@functools.partial(jax.jit, static_argnames=("n_queries", "ks"))
def max_dcg(qid, start, src, label, gains, *, n_queries: int, ks):
    """[queries, len(ks)]: DCG of each query's labels in falling order
    (``src``: the order by -label), cut at each k (dcg_calculator.cpp
    CalMaxDCGAtK)."""
    (g,), rank = _ranked(qid, start, src, (gains[label],))
    d = g / jnp.log2(2.0 + rank.astype(jnp.float32))
    return jnp.stack([jax.ops.segment_sum(
        jnp.where(rank < k, d, 0.0), qid, num_segments=n_queries)
        for k in ks], axis=1)


@functools.partial(jax.jit, static_argnames=("n_queries", "ks"))
def ndcg(qid, start, src, label, gains, inv_max, *, n_queries: int, ks):
    """[len(ks)]: mean NDCG@k over the queries (``src``: the order by
    -score); a query with no relevant document counts 1
    (rank_metric.hpp)."""
    with jax.default_matmul_precision("highest"):
        (g,), rank = _ranked(qid, start, src, (gains[label],))
        d = g / jnp.log2(2.0 + rank.astype(jnp.float32))
        out = []
        for j, k in enumerate(ks):
            dcg = jax.ops.segment_sum(jnp.where(rank < k, d, 0.0), qid,
                                      num_segments=n_queries)
            out.append(jnp.mean(jnp.where(inv_max[:, j] > 0,
                                          dcg * inv_max[:, j], 1.0)))
        return jnp.stack(out)


@functools.partial(jax.jit, static_argnames=(
    "n_queries", "trunc", "block", "sigmoid", "norm", "round_pairs_to"))
def lambdas(qid, start, qlen, src, label, gains, inv_max, score, *,
            n_queries: int, trunc: int, block: int, sigmoid: float,
            norm: bool, round_pairs_to: str = ""):
    """LambdaRank's lambdas and hessians [rows] in row order
    (rank_objective.hpp GetGradientsForOneQuery); ``src`` is the order
    of the rows by (query, -score). ``round_pairs_to``
    (a narrower float's name) rounds each pair's lambda and hessian to
    it before they are summed: the control one precision below."""
    n = qid.shape[0]
    T = trunc
    sig = jnp.float32(sigmoid)
    (ss, ls), rank = _ranked(qid, start, src, (score, label))
    gs = gains[ls]
    # the top T rows of every query, [queries, T]
    I = jnp.arange(T, dtype=jnp.int32)
    at = jnp.minimum(start[:, None] + I[None, :], n - 1)
    top_ok = I[None, :] < qlen[:, None]
    top_s, top_l, top_g = ss[at], ls[at], gs[at]
    best = ss[start]
    worst = ss[start + jnp.maximum(qlen - 1, 0)]
    spread = best != worst
    disc_i = 1.0 / jnp.log2(2.0 + I.astype(jnp.float32))

    nb = -(-n // block)
    pad = nb * block - n

    def rows(a, fill=0):
        return jnp.pad(a, (0, pad), constant_values=fill).reshape(nb, block)

    def one(xs):
        q, r, s_j, l_j, g_j, live = xs
        # row j (rank r) against the rows of rank i < min(r, T)
        ok = (I[None, :] < r[:, None]) & top_ok[q] & live[:, None] \
            & (top_l[q] != l_j[:, None])
        disc_j = 1.0 / jnp.log2(2.0 + r.astype(jnp.float32))
        delta = jnp.abs(top_g[q] - g_j[:, None]) \
            * jnp.abs(disc_i[None, :] - disc_j[:, None]) * inv_max[q][:, None]
        hi_i = top_l[q] > l_j[:, None]
        ds = jnp.where(hi_i, top_s[q] - s_j[:, None], s_j[:, None] - top_s[q])
        if norm:
            delta = jnp.where(spread[q][:, None],
                              delta / (0.01 + jnp.abs(ds)), delta)
        p0 = 1.0 / (1.0 + jnp.exp(sig * ds))
        m = ok.astype(jnp.float32)
        p_l = -sig * delta * p0 * m
        p_h = sig * sig * delta * p0 * (1.0 - p0) * m
        if round_pairs_to:
            p_l = gbdt_ref.round_to(p_l, round_pairs_to)
            p_h = gbdt_ref.round_to(p_h, round_pairs_to)
        to_i = jnp.where(hi_i, p_l, -p_l)      # the higher label: + p_l
        return (jnp.sum(-to_i, axis=1), jnp.sum(p_h, axis=1),
                jax.ops.segment_sum(to_i, q, num_segments=n_queries),
                jax.ops.segment_sum(p_h, q, num_segments=n_queries),
                jax.ops.segment_sum(jnp.sum(p_l, axis=1), q,
                                    num_segments=n_queries))

    def step(carry, xs):
        ti, th, tl = carry
        lj, hj, a, b, c = one(xs)
        return (ti + a, th + b, tl + c), (lj, hj)

    zero = jnp.zeros((n_queries, T), jnp.float32)
    (top_lam, top_hes, sum_pl), (lam_j, hes_j) = jax.lax.scan(
        step, (zero, zero, jnp.zeros((n_queries,), jnp.float32)),
        (rows(qid), rows(rank), rows(ss), rows(ls), rows(gs),
         rows(jnp.ones((n,), bool), False)))
    lam = lam_j.reshape(-1)[:n]
    hes = hes_j.reshape(-1)[:n]
    # a top row's share as the "i" of its pairs; rows are sorted within
    # their query only, so the sorted rows' queries are ``qid`` itself
    in_top = rank < T
    r_c = jnp.minimum(rank, T - 1)
    lam = lam + jnp.where(in_top, top_lam[qid, r_c], 0.0)
    hes = hes + jnp.where(in_top, top_hes[qid, r_c], 0.0)
    if norm:
        sum_l = -2.0 * sum_pl
        nf = jnp.where(sum_l > 0, jnp.log2(1.0 + sum_l)
                       / jnp.maximum(sum_l, _KEPS), 1.0)
        lam = lam * nf[qid]
        hes = hes * nf[qid]
    # back to row order
    return (jnp.zeros_like(lam).at[src].set(lam),
            jnp.zeros_like(hes).at[src].set(hes))


class Ranking:
    """One table's query structure on the device: query of each row,
    starts, lengths, labels, and the inverse max DCGs it needs."""

    def __init__(self, lengths: np.ndarray, label: np.ndarray,
                 trunc: int, eval_at: Sequence[int]):
        ln = np.asarray(lengths, np.int64)
        self.n_queries = len(ln)
        self.rows = int(ln.sum())
        self._qid = np.repeat(np.arange(self.n_queries, dtype=np.int32), ln)
        self._start = (np.cumsum(ln) - ln).astype(np.int32)
        self.qid = jnp.asarray(self._qid)
        self.start = jnp.asarray(self._start)
        self.qlen = jnp.asarray(ln.astype(np.int32))
        self.label = jnp.asarray(np.asarray(label).astype(np.int32))
        self.gains = jnp.asarray(label_gain())
        self.ks = tuple(int(k) for k in eval_at)
        mx = max_dcg(self.qid, self.start,
                     self.order(-np.asarray(label, np.float32)), self.label,
                     self.gains, n_queries=self.n_queries,
                     ks=(int(trunc),) + self.ks)
        inv = jnp.where(mx > 0, 1.0 / jnp.where(mx > 0, mx, 1.0), 0.0)
        self.inv_max_trunc = inv[:, 0]
        self.inv_max_at = inv[:, 1:]

    def order(self, key) -> Any:
        """The rows' stable order by (query, ``key``), on the device."""
        return jnp.asarray(host_order(self._qid, self._start,
                                      np.asarray(key)))

    def lambdas(self, score, params: Dict[str, Any], block: int = 1 << 18,
                round_pairs_to: str = ""):
        score = jnp.asarray(score, jnp.float32)
        return lambdas(
            self.qid, self.start, self.qlen, self.order(-np.asarray(score)),
            self.label, self.gains, self.inv_max_trunc, score,
            n_queries=self.n_queries,
            trunc=int(params.get("lambdarank_truncation_level", 30)),
            block=min(block, _round_up(self.rows, 8)),
            sigmoid=float(params.get("sigmoid", 1.0)),
            norm=bool(params.get("lambdarank_norm", True)),
            round_pairs_to=round_pairs_to)

    def ndcg(self, score) -> np.ndarray:
        return np.asarray(ndcg(self.qid, self.start,
                               self.order(-np.asarray(score)), self.label,
                               self.gains, self.inv_max_at,
                               n_queries=self.n_queries, ks=self.ks),
                          np.float64)


@functools.partial(
    jax.jit,
    static_argnames=("sub", "n_bins", "n_feat", "operand_dtype", "do_hist"),
    donate_argnames=("acc",))
def _tree_pass(bins, grad, hess, valid, featsel, thr_col, P, plen, acc, *,
               sub: int, n_bins: int, n_feat: int, operand_dtype: str,
               do_hist: bool):
    """gbdt_ref._tree_pass with the gradients handed in: each row's leaf
    in this tree, the per-leaf sums and (``do_hist``) histograms of one
    uploaded block of rows."""
    n = bins.shape[1]
    L = P.shape[0]

    def block(acc, i):
        lo = i * sub
        b = jax.lax.dynamic_slice_in_dim(bins, lo, sub, axis=1)
        m = jax.lax.dynamic_slice_in_dim(valid, lo, sub)
        g = jax.lax.dynamic_slice_in_dim(grad, lo, sub) * m
        h = jax.lax.dynamic_slice_in_dim(hess, lo, sub) * m
        oh = _leaf_onehot(b, featsel, thr_col, P, plen)         # [L, R]
        leaf_id = jnp.argmax(oh, axis=0).astype(jnp.uint8)
        ohm = oh & (m > 0)[None, :]
        terms = _split_terms(jnp.stack([g, h]), operand_dtype)
        vals = jnp.concatenate([t_[0:1] for t_ in terms]
                               + [t_[1:2] for t_ in terms], axis=0)  # [C, R]
        C = vals.shape[0]
        A = (ohm[None, :, :].astype(jnp.bfloat16)
             * vals[:, None, :]).reshape(C * L, sub)
        sums = jnp.sum(A.astype(jnp.float32), axis=1)
        cnt = jnp.sum(ohm.astype(jnp.int32), axis=1)
        a_sum, a_sc, a_cnt, a_h, a_hc = acc
        a_sum, a_sc = _kahan_add(a_sum, a_sc, sums)
        a_cnt = a_cnt + cnt
        if do_hist:
            bi = b[:n_feat].astype(jnp.int32)
            Bm = (bi[:, None, :] == jnp.arange(
                n_bins, dtype=jnp.int32)[None, :, None]) \
                .astype(jnp.bfloat16).reshape(n_feat * n_bins, sub)
            part = jax.lax.dot_general(
                A, Bm, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            a_h, a_hc = _kahan_add(a_h, a_hc, part)
        return (a_sum, a_sc, a_cnt, a_h, a_hc), leaf_id

    acc, leaf = jax.lax.scan(block, acc, jnp.arange(n // sub))
    return leaf.reshape(n), acc


@functools.partial(jax.jit, static_argnames=("sub",))
def _move(score, leaf, vals, *, sub: int):
    return score + jax.lax.map(
        lambda a: _leaf_value_of_row(a.astype(jnp.int32), vals),
        leaf.reshape(-1, sub)).reshape(-1)


@functools.partial(jax.jit, static_argnames=("sub",))
def _walk(bins, featsel, thr_col, P, plen, *, sub: int):
    """Leaf of every row of one uploaded block (no sums)."""
    return jax.lax.map(
        lambda b: jnp.argmax(_leaf_onehot(b, featsel, thr_col, P, plen),
                             axis=0).astype(jnp.uint8),
        bins.reshape(bins.shape[0], -1, sub).transpose(1, 0, 2)).reshape(-1)


class RankFollower(gbdt_ref.Follower):
    """gbdt_ref.Follower (its binning, its bin counts, its judgement of
    the splits) following LambdaRank trees: gradients from ``Ranking``,
    no initial score, and a held-out table walked beside the training
    one, its NDCG taken after every tree."""

    def _flat(self, per_block: List) -> Any:
        return jnp.concatenate([a[:b["k"]] for a, b
                                in zip(per_block, self.blocks)])

    def _blocks_of(self, flat) -> List:
        out, lo = [], 0
        for b in self.blocks:
            a = flat[lo:lo + b["k"]]
            out.append(jnp.pad(a, (0, b["y"].shape[0] - b["k"])))
            lo += b["k"]
        return out

    def walk(self, tt: TreeTables, vals) -> List:
        """Per block the leaf outputs ``vals`` each row of this follower
        (the held-out one) takes from tree ``tt``."""
        tabs = [jnp.asarray(a) for a in
                (tt.featsel, tt.thr_col, tt.P, tt.plen)]
        return [_move(jnp.zeros(b["y"].shape, jnp.float32),
                      _walk(b["bins"], *tabs, sub=self.sub), vals,
                      sub=self.sub) for b in self.blocks]

    def follow_rank(self, trees: Sequence[Dict[str, Any]], hist_trees: int,
                    rank: Ranking, held: "RankFollower", held_rank: Ranking,
                    operand_dtype: str = "float32",
                    round_pairs_to: str = "") -> Dict[str, Any]:
        prm = self.params
        lr = float(prm["learning_rate"])
        l2 = float(prm.get("lambda_l2", 0.0))
        L, sub = self.L, self.sub
        nterm = 3 if operand_dtype == "float32" else 1
        C = 2 * nterm
        score = jnp.zeros((self.rows,), jnp.float32)
        held_score = jnp.zeros((held.rows,), jnp.float32)
        out_trees, ndcgs = [], []
        with jax.default_matmul_precision("highest"):
            for t, tree in enumerate(trees):
                tt = TreeTables(tree, self.edges, L)
                do_hist = t < hist_trees
                lam, hes = rank.lambdas(score, prm,
                                        round_pairs_to=round_pairs_to)
                g_blk, h_blk = self._blocks_of(lam), self._blocks_of(hes)
                hshape = (C * L, self.n_feat * self.n_bins_pad) \
                    if do_hist else (1, 1)
                acc = (jnp.zeros((C * L,), jnp.float32),
                       jnp.zeros((C * L,), jnp.float32),
                       jnp.zeros((L,), jnp.int32),
                       jnp.zeros(hshape, jnp.float32),
                       jnp.zeros(hshape, jnp.float32))
                tabs = [jnp.asarray(a) for a in
                        (tt.featsel, tt.thr_col, tt.P, tt.plen)]
                leaves = []
                for bi, b in enumerate(self.blocks):
                    leaf, acc = _tree_pass(
                        b["bins"], g_blk[bi], h_blk[bi], b["valid"], *tabs,
                        acc, sub=sub, n_bins=self.n_bins_pad,
                        n_feat=self.n_feat, operand_dtype=operand_dtype,
                        do_hist=do_hist)
                    leaves.append(leaf)
                sums = np.asarray(acc[0], np.float64).reshape(C, L)
                G = sums[:nterm].sum(axis=0)[:tt.n_leaf]
                H = sums[nterm:].sum(axis=0)[:tt.n_leaf]
                cnt = np.asarray(acc[2], np.int64)[:tt.n_leaf]
                with np.errstate(divide="ignore", invalid="ignore"):
                    step = np.where(cnt > 0, -G / (H + l2) * lr, 0.0)
                rec: Dict[str, Any] = {
                    "tables": tt, "leaf_G": G, "leaf_H": H,
                    "leaf_count": cnt, "leaf_step": step,
                    "leaf_value": step, "bias": 0.0,
                    "node_count": tt.member.astype(np.int64) @ cnt}
                if do_hist:
                    hist = np.asarray(acc[3], np.float64).reshape(
                        C, L, self.n_feat, self.n_bins_pad)
                    rec.update(self._judge_splits(
                        tt, hist[:nterm].sum(axis=0)[:tt.n_leaf],
                        hist[nterm:].sum(axis=0)[:tt.n_leaf], cnt, prm))
                out_trees.append(rec)
                pv = np.zeros(L, np.float32)
                pv[:tt.n_leaf] = step
                vals = jnp.asarray(pv)
                score = score + self._flat(
                    [_move(jnp.zeros(b["y"].shape, jnp.float32), leaf, vals,
                           sub=sub) for leaf, b in zip(leaves, self.blocks)])
                held_score = held_score + held._flat(held.walk(tt, vals))
                ndcgs.append(held_rank.ndcg(held_score))
        return {"trees": out_trees, "score": score, "held_score": held_score,
                "ndcg": np.stack(ndcgs), "init": 0.0}
