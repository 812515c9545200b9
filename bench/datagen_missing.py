"""Seeded synthetic production-line measurements: a wide float32 table
most of whose cells are missing, in blocks.

Data kind ``station_missing`` (a cell's file gives the parameters under
"data"): ``rows``, ``cols``, ``stations``, ``missing_share``. The columns
are shared out among the stations (every station has one at least, the
sizes uneven); a part visits station s with probability p_s, on its own,
and all of a station's measurements are present or NaN together. The p_s
are drawn uneven and scaled so that the expected share of NaN cells is
``missing_share``; none is under 0.02, so no column is all NaN. Present
values are standard normal float32. There is no label: the table is
scored, never fitted. Rows are drawn in fixed blocks, each from its own
child of ``SeedSequence([seed, rows, cols, stations])``, so the same seed
gives the same bits whatever the number of threads.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np

BLOCK = 1 << 16
KIND = "station_missing"
MIN_VISIT = 0.02


def layout(seed: int, spec: Dict) -> Dict[str, np.ndarray]:
    """The line: ``station_of_col`` [cols] (sorted) and ``visit_p``
    [stations], from the seed."""
    rows, cols = int(spec["rows"]), int(spec["cols"])
    stations = min(int(spec["stations"]), cols)
    root = np.random.SeedSequence([int(seed), rows, cols, stations])
    g = np.random.Generator(np.random.PCG64(root.spawn(1)[0]))
    # one column each, the rest by uneven shares
    share = g.gamma(2.0, size=stations)
    extra = g.choice(stations, size=cols - stations, p=share / share.sum())
    station_of_col = np.sort(np.concatenate([np.arange(stations), extra]))
    width = np.bincount(station_of_col, minlength=stations)
    # visit probabilities: uneven, then scaled until the column-weighted
    # mean is the present share, every one inside [MIN_VISIT, 0.98]
    present = 1.0 - float(spec["missing_share"])
    p = g.beta(1.2, 3.0, size=stations)
    for _ in range(64):
        p = np.clip(p * present / (np.sum(p * width) / cols),
                    MIN_VISIT, 0.98)
    return {"station_of_col": station_of_col, "visit_p": p}


def make(seed: int, spec: Dict, threads: int = 8) -> np.ndarray:
    if spec.get("kind") != KIND:
        raise ValueError(f"unknown data kind {spec.get('kind')!r}")
    rows, cols = int(spec["rows"]), int(spec["cols"])
    line = layout(seed, spec)
    p = line["visit_p"]
    stations = len(p)
    width = np.bincount(line["station_of_col"], minlength=stations)
    n_blocks = -(-rows // BLOCK)
    children = np.random.SeedSequence(
        [int(seed), rows, cols, stations]).spawn(n_blocks + 1)[1:]
    X = np.empty((rows, cols), np.float32)

    def fill(i: int) -> None:
        xb = X[i * BLOCK:min((i + 1) * BLOCK, rows)]
        g = np.random.Generator(np.random.PCG64(children[i]))
        visited = g.random((len(xb), stations)) < p[None, :]
        present = np.repeat(visited, width, axis=1)   # columns are sorted
        xb.fill(np.nan)
        xb[present] = g.standard_normal(int(present.sum()),
                                        dtype=np.float32)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(fill, range(n_blocks)))
    return X
