"""Ranking objectives: LambdaRank (NDCG-weighted pairwise) and RankXENDCG.

Faithful ports of src/objective/rank_objective.hpp:26-370 (the reference
parallelizes per query with OpenMP; the CUDA backend has per-query device
kernels, cuda/cuda_rank_objective.cu).

LambdaRank runs ON DEVICE: queries are bucketed by padded length (the
ranking analog of sequence bucketing; metrics/rank_buckets.py owns the
layout, the NDCG metric reads through the same one), each bucket's
scores are gathered into a dense [num_queries, padded_len] block with
FIXED index matrices, and the per-query ranking (by counting, nothing is
sorted) + truncated pair-block lambda accumulation is pure vectorized
jnp — both pair-sides reduce along the pair axes, so no scatter is
needed. A bucket too large for
the pair block's budget is walked in blocks of queries (lax.map). This
removes the per-iteration host score pull the host path needs (gbdt
boost()).

RankXENDCG stays host-side: it draws fresh uniforms every iteration
(rank_objective.hpp:330), which doesn't fit the stateless device
objective interface yet.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..utils.log import log_fatal
from . import ObjectiveFunction
from ..metrics.rank_buckets import (RANK_TEMPS, bucket_labels,
                                    build_buckets, gather_scores,
                                    inverse_max_dcg_at, label_gains,
                                    map_blocks, queries_per_block,
                                    rank_by_score)
from ..metrics.rank_utils import default_label_gain
from ..runtime.profiler import count as span_count, span

_KEPS = 1e-15


def _sum_cols(x):
    """[nq, T, plen] -> [nq, T]: the sum over the last axis (a power of
    two) as a fixed tree of halves. ``jnp.sum`` leaves the order of the
    additions to the compiler, which picks another inside the boosting
    scan than in a program of its own; with the order written down the
    scan and the per-iteration loop get the same bits."""
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x[..., 0]


def _sum_rows(x):
    """[nq, T, ...] -> [nq, ...]: the sum over the T pair rows, one
    after the other (the order written down, as ``_sum_cols``)."""
    return jax.lax.fori_loop(
        1, x.shape[1],
        lambda i, acc: acc + jax.lax.dynamic_index_in_dim(
            x, i, axis=1, keepdims=False),
        x[:, 0])


class RankingObjective(ObjectiveFunction):
    """Base (reference: rank_objective.hpp:37)."""
    runs_on_host = True

    def __init__(self, config: Config):
        super().__init__(config)
        self.seed = config.objective_seed

    def init(self, metadata, num_data: int) -> None:
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            log_fatal("Ranking tasks require query information")
        self.query_boundaries = metadata.query_boundaries
        self.num_queries = len(self.query_boundaries) - 1

    def get_gradients_numpy(self, score: np.ndarray
                            ) -> Tuple[np.ndarray, np.ndarray]:
        score = np.asarray(score, np.float64).reshape(-1)
        grad = np.zeros(self.num_data, dtype=np.float32)
        hess = np.zeros(self.num_data, dtype=np.float32)
        qb = self.query_boundaries
        for q in range(self.num_queries):
            s, e = int(qb[q]), int(qb[q + 1])
            g, h = self._one_query(q, self.label[s:e], score[s:e])
            grad[s:e] = g
            hess[s:e] = h
        if self.weight is not None:
            grad *= self.weight
            hess *= self.weight
        return grad, hess

    def _one_query(self, qid, label, score):
        raise NotImplementedError


class LambdarankNDCG(RankingObjective):
    """reference: rank_objective.hpp:137-300."""
    name = "lambdarank"

    def __init__(self, config: Config):
        super().__init__(config)
        self.sigmoid = config.sigmoid
        if self.sigmoid <= 0:
            log_fatal(f"Sigmoid param {self.sigmoid} should be greater than zero")
        self.norm = config.lambdarank_norm
        self.truncation_level = config.lambdarank_truncation_level
        if len(config.label_gain):
            self.label_gain = np.asarray(config.label_gain, np.float64)
        else:
            self.label_gain = default_label_gain()

    # live float32 cells of one block of the pair computation: about a
    # dozen [queries, min(plen - 1, truncation), plen] temporaries, held
    # under this many bytes whatever a bucket holds
    pair_block_bytes = 256 << 20
    _PAIR_TEMPS = 16

    def init(self, metadata, num_data: int) -> None:
        super().init(metadata, num_data)
        if np.any(self.label < 0):
            log_fatal("Label should be non-negative for lambdarank")
        if int(np.max(self.label)) >= len(self.label_gain):
            log_fatal("Label exceeds label_gain size; set label_gain")
        with span("objective/init", queries=self.num_queries,
                  rows=num_data):
            self._build_device_buckets()

    # -- device path -------------------------------------------------
    runs_on_host = False

    def _pair_rows(self, plen: int) -> int:
        return min(plen - 1, self.truncation_level)

    def _build_device_buckets(self) -> None:
        """Per bucket of ``rank_buckets.build_buckets`` the FIXED device
        matrices the gradients read: row indices into the flat score
        vector, label gains / ids, query lengths, inverse max DCGs at
        the truncation level (Init, rank_objective.hpp:160-178); and the
        fixed inverse map from bucket cells back to rows. They reach
        the jitted programs as ARGUMENTS (``device_state``), not as
        constants of the lowered text."""
        budget = self.pair_block_bytes // 4

        def per_block(plen: int) -> int:
            return queries_per_block(
                max(self._pair_rows(plen) * plen * self._PAIR_TEMPS,
                    plen * plen * RANK_TEMPS), budget)

        buckets, pos_of_row = build_buckets(
            self.query_boundaries, self.num_data, per_block)
        self.inverse_max_dcgs = np.zeros(self.num_queries)
        dev = []
        cells = pair_cells = 0
        for bk in buckets:
            lab = bucket_labels(bk, self.label)
            imd = inverse_max_dcg_at(lab, self.label_gain,
                                   [self.truncation_level])[..., 0]
            live = bk["qids"] >= 0
            self.inverse_max_dcgs[bk["qids"][live]] = imd[live]
            dev.append({"idx": jnp.asarray(bk["idx"]),
                        "gain": jnp.asarray(
                            label_gains(lab, self.label_gain)),
                        "lab": jnp.asarray(lab),
                        "cnt": jnp.asarray(bk["cnt"]),
                        "imd": jnp.asarray(imd.astype(np.float32))})
            cells += lab.size
            pair_cells += bk["cnt"].size * self._pair_rows(bk["plen"]) \
                * bk["plen"]
        self._buckets = buckets
        self._state = {"buckets": tuple(dev),
                       "pos_of_row": jnp.asarray(pos_of_row)}
        span_count(padded_rows=cells, buckets=len(buckets),
                   pair_cells=pair_cells)

    def device_state(self):
        return self._state

    def _pair_block(self, score, bk):
        """One block of one bucket: ``bk`` arrays are [nq, plen] / [nq].
        Returns the lambdas and hessians of its cells, in row order.

        Nothing is sorted: each cell gets its rank by counting
        (``rank_by_score``); the rows of rank i < T, the "i" of
        LightGBM's pairs, are picked out by a one-hot over the cells
        (a sum with one term: exact), and every pair (i, j) with
        rank_j > i is evaluated with j left where it is."""
        sig = jnp.float32(self.sigmoid)
        plen = bk["idx"].shape[-1]
        cnt = bk["cnt"]
        s = gather_scores(score, bk["idx"])                 # [nq, plen]
        rank, has_row = rank_by_score(s, cnt)
        gn, lb = bk["gain"], bk["lab"]
        Ti = self._pair_rows(plen)
        Ii = jnp.arange(Ti, dtype=jnp.int32)
        sel = rank[:, None, :] == Ii[None, :, None]         # [nq, Ti, plen]
        has_i = Ii[None, :] < cnt[:, None]                  # [nq, Ti]

        def top(a):            # the value of the row of rank i
            return jnp.sum(jnp.where(sel, a[:, None, :], 0), axis=2)

        top_s, top_g, top_l = top(s), top(gn), top(lb)
        pair_ok = (has_i[:, :, None] & has_row[:, None, :]
                   & (rank[:, None, :] > Ii[None, :, None])
                   & (top_l[:, :, None] != lb[:, None, :]))
        disc_i = 1.0 / jnp.log2(2.0 + Ii.astype(jnp.float32))
        disc_j = 1.0 / jnp.log2(2.0 + rank.astype(jnp.float32))
        dcg_gap = jnp.abs(top_g[:, :, None] - gn[:, None, :])
        pdisc = jnp.abs(disc_i[None, :, None] - disc_j[:, None, :])
        delta_ndcg = dcg_gap * pdisc * bk["imd"][:, None, None]
        hi_is_i = top_l[:, :, None] > lb[:, None, :]
        dscore = jnp.where(hi_is_i,
                           top_s[:, :, None] - s[:, None, :],
                           s[:, None, :] - top_s[:, :, None])
        if self.norm:
            best = top_s[:, 0]
            worst = jnp.sum(jnp.where(
                has_row & (rank == (cnt - 1)[:, None]), s, 0.0), axis=1)
            do_norm = (best != worst)[:, None, None]
            delta_ndcg = jnp.where(
                do_norm, delta_ndcg / (0.01 + jnp.abs(dscore)),
                delta_ndcg)
        p0 = 1.0 / (1.0 + jnp.exp(sig * dscore))
        m = pair_ok.astype(jnp.float32)
        p_l = -sig * delta_ndcg * p0 * m
        p_h = sig * sig * delta_ndcg * p0 * (1.0 - p0) * m
        p_s = jnp.where(hi_is_i, p_l, -p_l)    # as the pair's row i gets it
        # the products are whole before they are summed: a compiler that
        # fused the last multiply into the sums (one rounding for two)
        # would do so in one program and not in another, and the scan
        # and the per-iteration loop would grow different trees
        p_s, p_h = jax.lax.optimization_barrier((p_s, p_h))
        # both pair sides reduce along an axis — no scatter
        li = _sum_cols(p_s)                                  # [nq, Ti]
        ljc = -_sum_rows(p_s)                                # [nq, plen]
        hic = _sum_cols(p_h)
        hjc = _sum_rows(p_h)
        # row i's side goes back to its cell through the same one-hot
        lam = ljc + _sum_rows(jnp.where(sel, li[:, :, None], 0.0))
        hes = hjc + _sum_rows(jnp.where(sel, hic[:, :, None], 0.0))
        if self.norm:
            # p_l is never positive, so the pairs' |p_s| are its negatives
            sum_l = 2.0 * _sum_rows(_sum_cols(jnp.abs(p_s)))
            nf = jnp.where(sum_l > 0,
                           jnp.log2(1.0 + sum_l)
                           / jnp.maximum(sum_l, _KEPS), 1.0)
            lam *= nf[:, None]
            hes *= nf[:, None]
        return lam, hes

    def get_gradients(self, score, label, weight, state=None):
        """Device lambdarank (GetGradientsForOneQuery,
        rank_objective.hpp:188-260, vectorized over bucketed queries).
        ``state`` is ``device_state()`` handed through a jitted
        program's arguments; left out, the arrays are read off the
        objective (and become constants of whatever traces the call)."""
        if state is None:
            state = self._state
        n_pad = score.shape[0]
        outs_g, outs_h = [], []
        with jax.named_scope("lgbm_rank_grad"):
            for bk in state["buckets"]:
                lam, hes = map_blocks(
                    lambda b: self._pair_block(score, b), bk)
                outs_g.append(lam.reshape(-1))
                outs_h.append(hes.reshape(-1))
            gflat = jnp.concatenate(outs_g)
            hflat = jnp.concatenate(outs_h)
            g = gflat[state["pos_of_row"]]
            h = hflat[state["pos_of_row"]]
            if weight is not None:
                w = weight[:g.shape[0]]
                g, h = g * w, h * w
            if n_pad > g.shape[0]:
                pad = n_pad - g.shape[0]
                g = jnp.pad(g, (0, pad))
                h = jnp.pad(h, (0, pad))
        return g, h

    def _one_query(self, qid, label, score):
        cnt = len(label)
        lambdas = np.zeros(cnt)
        hessians = np.zeros(cnt)
        if cnt <= 1:
            return lambdas, hessians
        inv_max_dcg = self.inverse_max_dcgs[qid]
        sorted_idx = np.argsort(-score, kind="stable")
        ls = label[sorted_idx].astype(np.int64)
        ss = score[sorted_idx]
        best_score, worst_score = ss[0], ss[-1]
        T = min(cnt - 1, self.truncation_level)
        # pair block: i in [0, T), j in (i, cnt)
        I = np.arange(T)
        J = np.arange(cnt)
        valid = (J[None, :] > I[:, None]) & (ls[None, :cnt] != ls[:T, None])
        if not valid.any():
            return lambdas, hessians
        gain = self.label_gain[ls]
        disc = 1.0 / np.log2(2.0 + np.arange(cnt))
        dcg_gap = np.abs(gain[:T, None] - gain[None, :])
        paired_disc = np.abs(disc[:T, None] - disc[None, :])
        delta_ndcg = dcg_gap * paired_disc * inv_max_dcg
        # delta_score = high_score - low_score; high = larger label
        hi_is_i = ls[:T, None] > ls[None, :]
        delta_score = np.where(hi_is_i, ss[:T, None] - ss[None, :],
                               ss[None, :] - ss[:T, None])
        if self.norm and best_score != worst_score:
            delta_ndcg = delta_ndcg / (0.01 + np.abs(delta_score))
        sig = self.sigmoid
        p0 = 1.0 / (1.0 + np.exp(sig * delta_score))
        p_lambda = -sig * delta_ndcg * p0 * valid
        p_hessian = sig * sig * delta_ndcg * p0 * (1.0 - p0) * valid
        # scatter back: high += p_lambda, low -= p_lambda; both += p_hessian
        hi_idx = np.where(hi_is_i, sorted_idx[:T, None],
                          sorted_idx[None, :cnt])
        lo_idx = np.where(hi_is_i, sorted_idx[None, :cnt],
                          sorted_idx[:T, None])
        np.add.at(lambdas, hi_idx.ravel(), p_lambda.ravel())
        np.add.at(lambdas, lo_idx.ravel(), -p_lambda.ravel())
        np.add.at(hessians, hi_idx.ravel(), p_hessian.ravel())
        np.add.at(hessians, lo_idx.ravel(), p_hessian.ravel())
        sum_lambdas = -2.0 * float(np.sum(p_lambda))
        if self.norm and sum_lambdas > 0:
            nf = np.log2(1 + sum_lambdas) / sum_lambdas
            lambdas *= nf
            hessians *= nf
        return lambdas, hessians

    def to_string(self):
        return "lambdarank"


class RankXENDCG(RankingObjective):
    """Cross-entropy NDCG surrogate (reference: rank_objective.hpp:302-370)."""
    name = "rank_xendcg"

    def init(self, metadata, num_data: int) -> None:
        super().init(metadata, num_data)
        self._rng = np.random.RandomState(self.seed)

    def _one_query(self, qid, label, score):
        cnt = len(label)
        if cnt <= 1:
            return np.zeros(cnt), np.zeros(cnt)
        s = score - np.max(score)
        rho = np.exp(s)
        rho /= np.sum(rho)
        # Phi(l, g) = 2^l - g  (uniform g per doc)
        params = np.power(2.0, label.astype(np.int64)) \
            - self._rng.uniform(size=cnt)
        inv_denominator = 1.0 / max(_KEPS, float(np.sum(params)))
        # first order
        term1 = -params * inv_denominator + rho
        lambdas = term1.copy()
        params = term1 / (1.0 - rho)
        sum_l1 = float(np.sum(params))
        # second order
        term2 = rho * (sum_l1 - params)
        lambdas += term2
        params = term2 / (1.0 - rho)
        sum_l2 = float(np.sum(params))
        # third order
        lambdas += rho * (sum_l2 - params)
        hessians = rho * (1.0 - rho)
        return lambdas, hessians

    def to_string(self):
        return "rank_xendcg"
