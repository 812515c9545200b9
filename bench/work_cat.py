"""The histogram work of a training step on a table whose columns differ
in bin count, whatever implements it.

``work.py`` prices a row visit at one ``max_bin`` width for every
column; a categorical column maps to as many bins as its categories need
(up to 256: 8 bits), so here each column is priced at its own width, the
bits its ingest mapper's bin count takes. The visits are ``work.py``'s:
every row for the root, then the smaller child of every split. Kept with
the benchmark so that no change to the program can move it.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Sequence

import work


def bytes_per_visit(feature_bins: Sequence[int], grad_bytes: int) -> float:
    bits = sum(max(1, math.ceil(math.log2(max(int(nb), 2))))
               for nb in feature_bins)
    return bits / 8.0 + 2 * grad_bytes


def least_seconds(trees: Sequence[Dict[str, Any]], rows: int,
                  feature_bins: Sequence[int], grad_bytes: int,
                  peak: Dict[str, float]) -> Dict[str, Any]:
    """Least time one chip needs for these trees' histogram work, and
    which peak bounds it."""
    visits = sum(work.row_visits(t, rows) for t in trees)
    t_bytes = visits * bytes_per_visit(feature_bins, grad_bytes) \
        / peak["hbm_bytes_per_s"]
    t_ops = visits * work.ops_per_visit(len(feature_bins)) \
        / peak["bf16_flops_per_s"]
    return {"visits": visits, "seconds": max(t_bytes, t_ops),
            "bound_by": "bytes" if t_bytes >= t_ops else "operations",
            "bytes_s": t_bytes, "ops_s": t_ops}
