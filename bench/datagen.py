"""Seeded synthetic tabular data, made fast.

One general generator; a cell's file gives its parameters under "data":
``rows``, ``cols``, ``noise`` (standard deviation of the label noise) and
``kind`` ("linear_binary": standard-normal float32 features, a seeded
linear score plus noise thresholded at 0, as the repo's bench.py draws
its Higgs-like task). Rows are drawn in fixed blocks, each from its own
child of ``SeedSequence([seed, rows, cols])``, so the same seed gives the
same bits whatever the number of threads.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Tuple

import numpy as np

BLOCK = 1 << 20


def make(seed: int, spec: Dict, threads: int = 8
         ) -> Tuple[np.ndarray, np.ndarray]:
    if spec.get("kind", "linear_binary") != "linear_binary":
        raise ValueError(f"unknown data kind {spec.get('kind')!r}")
    rows, cols = int(spec["rows"]), int(spec["cols"])
    noise = np.float32(spec.get("noise", 0.5))
    n_blocks = -(-rows // BLOCK)
    children = np.random.SeedSequence(
        [int(seed), rows, cols]).spawn(n_blocks + 1)
    w = np.random.Generator(np.random.PCG64(children[0])) \
        .standard_normal(cols, dtype=np.float32)
    X = np.empty((rows, cols), np.float32)
    y = np.empty(rows, np.float32)

    def fill(i: int) -> None:
        lo, hi = i * BLOCK, min((i + 1) * BLOCK, rows)
        g = np.random.Generator(np.random.PCG64(children[i + 1]))
        g.standard_normal(out=X[lo:hi], dtype=np.float32)
        eps = g.standard_normal(hi - lo, dtype=np.float32)
        y[lo:hi] = (X[lo:hi] @ w + noise * eps) > 0

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(fill, range(n_blocks)))
    return X, y
