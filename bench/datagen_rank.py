"""Seeded synthetic learning-to-rank tables in the shape of MSLR-WEB30K.

A cell's file gives the parameters under "data" ("kind": "rank_streams")
and the kind calls ``make`` once for the training part and once for the
held-out part: ``rows``, ``queries``, ``cols`` (137: LightGBM counts the
file's absent index 0, so column 0 is constant zero and 136 are used),
``max_len`` (1251) and the shares below. What is fixed by the shape and
what by the seed:

* **query lengths**: the multiset is a fixed function of (rows,
  queries, max_len): the quantiles of a log-logistic law (shape
  ``len_shape``: heavy-tailed, 1 to ``max_len``, mean rows / queries),
  made to sum to ``rows`` exactly. The seed only shuffles which query
  gets which length. So every seed has the same count of queries of
  every padded length, and a trainer compiles for one set of shapes.
* **features**: five text streams (body, anchor, title, url, whole
  document) of 25 columns and 11 page columns. In a stream the first
  ``ints_per_stream`` columns are small counts (floor of a shifted
  normal, at 0 or above), the rest continuous and heavy-tailed
  (log-normal); the page block has ``page_ints`` counts. A stream is
  *empty* for a row with the probability ``empty_share`` gives it (the
  anchor stream mostly): its whole block reads 0.
* **relevance**: a seeded linear function of the latent normals (one
  weight vector a seed, shared by the training and the held-out part)
  plus per-query and per-row noise, cut into grades 0-4 at the normal
  quantiles of ``grade_share``.

Rows are drawn in fixed blocks, each from its own child of
``SeedSequence([seed, rows, cols, part])``, so the same seed gives the
same bits whatever the number of threads.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np

BLOCK = 1 << 15
STREAMS = 5
STREAM_COLS = 25
DEFAULTS = {
    "max_len": 1251, "len_shape": 2.5,
    "ints_per_stream": 10, "page_ints": 3,
    "empty_share": [0.0, 0.6, 0.05, 0.1, 0.0],
    "grade_share": [0.52, 0.32, 0.13, 0.02, 0.01],
    "query_noise": 0.5, "row_noise": 0.7, "tail": 0.8,
}


def _spec(spec: Dict) -> Dict:
    out = dict(DEFAULTS, **spec)
    if out.get("kind") != "rank_streams":
        raise ValueError(f"unknown data kind {out.get('kind')!r}")
    if int(out["cols"]) != 1 + STREAMS * STREAM_COLS + 11:
        raise ValueError("rank_streams makes 137 columns")
    return out


def query_lengths(rows: int, queries: int, max_len: int,
                  shape: float) -> np.ndarray:
    """The fixed multiset, longest last: quantiles of a log-logistic law
    clipped to [1, max_len], its scale found by bisection so that the
    floors sum to at most ``rows``; the remainder goes one row each to
    the shortest queries."""
    u = (np.arange(queries, dtype=np.float64) + 0.5) / queries
    q = (u / (1.0 - u)) ** (1.0 / shape)
    lo, hi = 0.0, float(max_len)
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        n = int(np.clip(np.floor(mid * q), 1, max_len).sum())
        lo, hi = (mid, hi) if n <= rows else (lo, mid)
    ln = np.clip(np.floor(lo * q), 1, max_len).astype(np.int64)
    left = rows - int(ln.sum())
    if left < 0 or left > queries:
        raise ValueError(f"{rows} rows do not fit {queries} queries of "
                         f"1 to {max_len}")
    ln[:left] += 1
    return ln


def column_kinds(spec: Dict) -> Dict[str, np.ndarray]:
    """Column indices by kind: "count" (small integers), "continuous"
    (heavy-tailed), and per stream its block (column 0 is in none)."""
    sp = _spec(spec)
    count: List[int] = []
    cont: List[int] = []
    blocks = []
    for s in range(STREAMS):
        c0 = 1 + s * STREAM_COLS
        k = int(sp["ints_per_stream"])
        count += list(range(c0, c0 + k))
        cont += list(range(c0 + k, c0 + STREAM_COLS))
        blocks.append(np.arange(c0, c0 + STREAM_COLS))
    p0 = 1 + STREAMS * STREAM_COLS
    count += list(range(p0, p0 + int(sp["page_ints"])))
    cont += list(range(p0 + int(sp["page_ints"]), int(sp["cols"])))
    return {"count": np.array(count),
            "continuous": np.array(cont), "streams": blocks}


def _runs(cols: np.ndarray) -> List[Tuple[int, int]]:
    """Sorted column indices as [start, stop) runs of neighbours."""
    cuts = np.flatnonzero(np.diff(cols) != 1) + 1
    return [(int(r[0]), int(r[-1]) + 1) for r in np.split(cols, cuts)]


def continuous_columns(spec: Dict) -> np.ndarray:
    """Continuous columns no stream of which is ever empty: the ones
    whose bins a binning of equal mass fills evenly."""
    sp = _spec(spec)
    kinds = column_kinds(sp)
    dead = np.concatenate([kinds["streams"][s] for s in range(STREAMS)
                           if sp["empty_share"][s] > 0] or [[]])
    return np.setdiff1d(kinds["continuous"], dead).astype(np.int64)


def make(seed: int, spec: Dict, part: int = 0, threads: int = 8
         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(X [rows, cols] float32, grades [rows] float32, query lengths
    [queries] int64). ``part`` 0 is the training table, 1 the held-out
    one: other rows and queries under the same weights."""
    sp = _spec(spec)
    rows, queries, cols = int(sp["rows"]), int(sp["queries"]), int(sp["cols"])
    kinds = column_kinds(sp)
    wgen = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), cols, 7])))
    w = wgen.standard_normal(cols).astype(np.float64)
    w[0] = 0.0
    alive = np.ones(cols)
    for s, blk in enumerate(kinds["streams"]):
        alive[blk] = 1.0 - float(sp["empty_share"][s])
    w /= np.sqrt(np.sum(w * w * alive))        # the signal has variance 1
    w32 = w.astype(np.float32)
    qn, rn = float(sp["query_noise"]), float(sp["row_noise"])
    std = np.sqrt(1.0 + qn * qn + rn * rn)
    cuts = np.array([NormalDist().inv_cdf(c) * std for c in
                     np.cumsum(sp["grade_share"])[:-1]], np.float32)

    n_blocks = -(-rows // BLOCK)
    children = np.random.SeedSequence(
        [int(seed), rows, cols, int(part)]).spawn(n_blocks + 1)
    g0 = np.random.Generator(np.random.PCG64(children[0]))
    lengths = query_lengths(rows, queries, int(sp["max_len"]),
                            float(sp["len_shape"]))
    lengths = lengths[g0.permutation(queries)]
    q_noise = (qn * g0.standard_normal(queries)).astype(np.float32)
    qid = np.repeat(np.arange(queries, dtype=np.int32), lengths)

    X = np.empty((rows, cols), np.float32)
    y = np.empty(rows, np.float32)
    tail = np.float32(sp["tail"])
    # each kind's columns as runs of neighbours: views, worked in place
    count_runs, cont_runs = _runs(kinds["count"]), _runs(kinds["continuous"])

    def fill(i: int) -> None:
        lo, hi = i * BLOCK, min((i + 1) * BLOCK, rows)
        g = np.random.Generator(np.random.PCG64(children[i + 1]))
        Z = X[lo:hi]
        g.standard_normal(out=Z, dtype=np.float32)
        t = Z @ w32 + rn * Z[:, 0] + q_noise[qid[lo:hi]]   # w32[0] is 0
        dead = []
        for s, blk in enumerate(kinds["streams"]):
            p = float(sp["empty_share"][s])
            if p > 0:
                d = g.random(hi - lo, dtype=np.float32) < p
                t -= d * (Z[:, blk[0]:blk[-1] + 1] @ w32[blk])
                dead.append((d, blk))
        for a, b in count_runs:
            v = Z[:, a:b]
            v *= np.float32(1.5)
            v += np.float32(2.0)
            np.maximum(v, np.float32(0.0), out=v)
            np.floor(v, out=v)
        for a, b in cont_runs:
            v = Z[:, a:b]
            v *= tail
            np.exp(v, out=v)
        for d, blk in dead:
            Z[:, blk[0]:blk[-1] + 1] *= ~d[:, None]
        Z[:, 0] = 0.0
        y[lo:hi] = np.searchsorted(cuts, t.astype(np.float32))

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(fill, range(n_blocks)))
    return X, y, lengths
