"""Query buckets: the one layout both the device LambdaRank objective
and the device NDCG metric read scores through.

Queries are grouped by padded length (powers of two from 8: the ranking
analog of sequence bucketing); a bucket is a dense [queries, plen] block
with FIXED index matrices into the flat score vector, so a per-query
ranking is one batched comparison and nothing is scattered. Built once on the
host, vectorised over queries (the only Python loop is over the handful
of distinct padded lengths).

A bucket whose per-query work would hold more cells live at once than
its consumer's budget is cut into equal blocks of queries
([blocks, queries a block, plen], the last block padded with empty
queries) that the consumer walks in turn (``map_blocks``).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MIN_PLEN = 8


def padded_lengths(lengths: np.ndarray) -> np.ndarray:
    """Smallest power of two >= each length, at least ``MIN_PLEN``."""
    ln = np.maximum(np.asarray(lengths, np.int64), 1)
    exp = np.ceil(np.log2(ln)).astype(np.int64)
    # log2 of an exact power of two is exact in float64; guard anyway
    exp += ((1 << exp) < ln)
    return np.maximum(1 << exp, MIN_PLEN)


def queries_per_block(cells_per_query: int, block_cells: int) -> int:
    """Queries a block may hold so that ``cells_per_query`` cells a query
    stay under ``block_cells`` in all (at least one query)."""
    return max(1, int(block_cells) // max(int(cells_per_query), 1))


def build_buckets(query_boundaries: np.ndarray, num_data: int,
                  per_block: Optional[Callable[[int], int]] = None
                  ) -> Tuple[List[Dict[str, Any]], np.ndarray]:
    """``[{plen, queries, qids, idx int32, cnt int32}]`` in rising
    ``plen``, queries in their own order inside a bucket, and
    ``pos_of_row [num_data] int32``: each row's place in the buckets'
    concatenated, flattened cells.

    ``qids``, ``idx`` and ``cnt`` are [nq], [nq, plen], [nq]; where
    ``per_block(plen)`` is smaller than the bucket's ``queries`` they are
    [blocks, per_block(plen), ...] instead, the tail made of empty
    queries (``qids`` -1, ``cnt`` 0). ``idx`` is ``num_data`` where a
    query has no row (``gather_scores`` clips it; ``cnt`` masks it)."""
    qb = np.asarray(query_boundaries, np.int64)
    lengths = np.diff(qb)
    plens = padded_lengths(lengths)
    buckets: List[Dict[str, Any]] = []
    pos_of_row = np.zeros(num_data, np.int32)
    offset = 0
    for plen in np.unique(plens):
        plen = int(plen)
        qids = np.flatnonzero(plens == plen)
        nq = len(qids)
        pb = per_block(plen) if per_block is not None else nq
        if pb < nq:                    # whole blocks: pad with empty queries
            qids = np.concatenate(
                [qids, np.full(-nq % pb, -1, qids.dtype)])
        live = qids >= 0
        cnt = np.where(live, lengths[np.maximum(qids, 0)], 0)
        col = np.arange(plen, dtype=np.int64)[None, :]
        has_row = col < cnt[:, None]
        idx = np.where(has_row, qb[np.maximum(qids, 0)][:, None] + col,
                       num_data)
        cell = offset + np.arange(len(qids), dtype=np.int64)[:, None] * plen \
            + col
        pos_of_row[idx[has_row]] = cell[has_row]
        shape = (-1, pb) if pb < nq else (len(qids),)
        buckets.append({"plen": plen, "queries": nq,
                        "qids": qids.reshape(shape),
                        "idx": idx.astype(np.int32).reshape(shape + (plen,)),
                        "cnt": cnt.astype(np.int32).reshape(shape)})
        offset += len(qids) * plen
    return buckets, pos_of_row


def bucket_labels(bucket: Dict[str, Any], label: np.ndarray) -> np.ndarray:
    """The bucket's labels as int32, shaped like ``idx``; -1 where a
    query has no row."""
    ext = np.concatenate([np.asarray(label).astype(np.int32),
                          np.full(1, -1, np.int32)])
    return ext[bucket["idx"]]


def max_dcgs(lab: np.ndarray, label_gain: np.ndarray,
             ks: Sequence[int]) -> np.ndarray:
    """[..., plen] labels (-1: no row) -> [..., len(ks)] float64: the DCG
    of each query's labels in falling order, cut at each ``k``, summed
    position by position as dcg_calculator.cpp CalMaxDCGAtK does."""
    top = np.sort(lab, axis=-1)[..., ::-1][..., :max(ks)]
    gain = np.where(top >= 0,
                    np.asarray(label_gain, np.float64)[np.maximum(top, 0)],
                    0.0)
    out = np.zeros(lab.shape[:-1] + (len(ks),))
    acc = np.zeros(lab.shape[:-1])
    for t in range(gain.shape[-1]):
        acc = acc + gain[..., t] / np.log2(t + 2.0)
        for j, k in enumerate(ks):
            if t < k:
                out[..., j] = acc
    return out


def inverse_max_dcg_at(lab: np.ndarray, label_gain: np.ndarray,
                     ks: Sequence[int]) -> np.ndarray:
    """1 / ``max_dcgs``, 0 where a query has no relevant document."""
    mx = max_dcgs(lab, label_gain, ks)
    return np.where(mx > 0, 1.0 / np.where(mx > 0, mx, 1.0), 0.0)


def label_gains(lab: np.ndarray, label_gain: np.ndarray) -> np.ndarray:
    """float32 gain of each cell's label; 0 where a query has no row."""
    return np.where(lab >= 0, np.asarray(label_gain)[np.maximum(lab, 0)],
                    0.0).astype(np.float32)


def map_blocks(fn, bucket):
    """``fn`` over a bucket's device arrays: in turn over its blocks of
    queries where ``build_buckets`` cut it ([blocks, queries, plen]),
    else once."""
    if bucket["idx"].ndim == 3:
        return jax.lax.map(fn, bucket)
    return fn(bucket)


def rank_by_score(score, cnt):
    """Each cell's rank among its query's rows in stable descending
    order of ``score`` [nq, plen] (``cnt`` [nq] rows a query): the count
    of rows that come before it, a higher score or an equal one from an
    earlier row (ties keep row order: at tree 1 every score is equal),
    as ``np.argsort(-score, kind="stable")`` places them. Cells past a
    query's length rank ``plen``. Also returns which cells hold a row.

    Counting, not sorting: ``plen`` x ``plen`` comparisons a query that
    the compiler folds into one reduction (integer sums: order free),
    where a sort of every bucket shape took the TPU's compiler seconds
    each to build and left the cells to be put back in row order."""
    plen = score.shape[1]
    pos = jnp.arange(plen, dtype=jnp.int32)
    has_row = pos[None, :] < cnt[:, None]
    mine, other = score[:, :, None], score[:, None, :]
    before = ((other > mine) | ((other == mine)
                               & (pos[None, None, :] < pos[None, :, None]))) \
        & has_row[:, None, :]
    rank = jnp.sum(before, axis=2, dtype=jnp.int32)
    return jnp.where(has_row, rank, plen), has_row


RANK_TEMPS = 2      # live plen x plen cells a query while it is ranked


def gather_scores(score, idx):
    """Flat scores -> [..., plen] through ``idx``. A cell with no row
    reads some finite score: every consumer masks it by ``cnt``."""
    return jnp.take(score.astype(jnp.float32), idx, mode="clip")
