"""The work a histogram GBDT step needs, whatever implements it.

Kept with the benchmark so that no change to the program can move it.
Per tree, the histogram algorithm has to visit every row once for the
root and, for every split, the rows of the smaller child (the larger
child's histogram is the parent's minus the smaller's): the reference's
own subtraction trick. A visit reads the row's bins, its gradient and its
hessian, and adds them into one bin per feature.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Sequence


def row_visits(tree: Dict[str, Any], rows: int) -> int:
    """rows + sum over splits of min(left count, right count), from a
    dumped tree's ``internal_count`` / ``leaf_count``."""
    total = int(rows)
    stack = [tree["tree_structure"]]
    while stack:
        node = stack.pop()
        if "split_index" not in node:
            continue
        kids = (node["left_child"], node["right_child"])
        counts = [int(k.get("internal_count", k.get("leaf_count", 0)))
                  for k in kids]
        total += min(counts)
        stack.extend(kids)
    return total


def bytes_per_visit(features: int, max_bin: int, grad_bytes: int) -> float:
    bits = math.ceil(math.log2(max_bin + 1))
    return features * bits / 8.0 + 2 * grad_bytes


def ops_per_visit(features: int) -> float:
    return 2.0 * features          # one gradient and one hessian add each


def least_seconds(trees: Sequence[Dict[str, Any]], rows: int, features: int,
                  max_bin: int, grad_bytes: int, peak: Dict[str, float]
                  ) -> Dict[str, Any]:
    """Least time one chip needs for these trees' histogram work, and
    which peak bounds it."""
    visits = sum(row_visits(t, rows) for t in trees)
    t_bytes = visits * bytes_per_visit(features, max_bin, grad_bytes) \
        / peak["hbm_bytes_per_s"]
    t_ops = visits * ops_per_visit(features) / peak["bf16_flops_per_s"]
    return {"visits": visits, "seconds": max(t_bytes, t_ops),
            "bound_by": "bytes" if t_bytes >= t_ops else "operations",
            "bytes_s": t_bytes, "ops_s": t_ops}


def score_least_seconds(rows: int, features: int, comparisons_per_row: float,
                        peak: Dict[str, float]) -> Dict[str, Any]:
    """Least time one chip needs to score ``rows`` float32 rows against a
    forest: each row's features read once and its margin written once,
    and one comparison and one add per level of every tree."""
    t_bytes = rows * (features * 4.0 + 4.0) / peak["hbm_bytes_per_s"]
    t_ops = rows * 2.0 * comparisons_per_row / peak["bf16_flops_per_s"]
    return {"seconds": max(t_bytes, t_ops),
            "bound_by": "bytes" if t_bytes >= t_ops else "operations",
            "bytes_s": t_bytes, "ops_s": t_ops}
