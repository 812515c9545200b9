"""A seeded forest of binary decision trees, as flat arrays and as
LightGBM model text.

To a scorer the forest is what weights are to a model: it is made from
the seed in the shape the configuration states, never trained here. A
configuration's file gives the parameters under "forest": ``trees``,
``min_leaves`` and ``max_leaves`` (a tree's leaf count is uniform between
them), ``leaf_scale``; the cell gives ``cols`` and ``rows``. Each tree is
drawn from its own child of ``SeedSequence([seed, trees, cols])`` and is
grown as LightGBM numbers a tree: split i takes a seeded leaf of the
i + 1 there are, the left child keeps the leaf's index and the right
child is leaf i + 1. A node's split feature is uniform over the columns,
its threshold a float64 standard normal (as the columns' present values
are drawn), its default direction a seeded bit, its missing type NaN
(every column of a station table has NaN, so a trained forest has no
other). Leaf values are normal times ``leaf_scale``. Counts and weights
(what a model file carries and no scorer reads) descend from ``rows`` by
seeded shares.

``make`` returns the arrays, padded to the widest tree ([T, J] a node,
[T, L] a leaf; children as LightGBM writes them, ``~leaf`` for a leaf);
``model_text`` writes them in the public interchange format
(gbdt_model_text.cpp), every float64 with 17 significant digits, so a
parser gives back the bits. It imports nothing of the program under test.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

MISSING_NONE, MISSING_ZERO, MISSING_NAN = 0, 1, 2
# hessian of a row under binary log-loss at the Bosch base rate
_HESSIAN = 0.0058 * 0.9942


def make(seed: int, spec: Dict[str, Any]) -> Dict[str, np.ndarray]:
    trees, cols = int(spec["trees"]), int(spec["cols"])
    lo, hi = int(spec["min_leaves"]), int(spec["max_leaves"])
    rows = int(spec.get("rows", 1_000_000))
    scale = float(spec.get("leaf_scale", 0.1))
    if not 2 <= lo <= hi:
        raise ValueError(f"leaf counts {lo}..{hi}")
    J, L = hi - 1, hi
    out = {"num_leaves": np.zeros(trees, np.int32),
           "split_feature": np.zeros((trees, J), np.int32),
           "threshold": np.zeros((trees, J), np.float64),
           "missing_type": np.zeros((trees, J), np.int8),
           "default_left": np.zeros((trees, J), bool),
           "categorical": np.zeros((trees, J), bool),
           "left_child": np.zeros((trees, J), np.int32),
           "right_child": np.zeros((trees, J), np.int32),
           "split_gain": np.zeros((trees, J), np.float32),
           "internal_count": np.zeros((trees, J), np.int64),
           "leaf_value": np.zeros((trees, L), np.float64),
           "leaf_count": np.zeros((trees, L), np.int64)}
    children = np.random.SeedSequence([int(seed), trees, cols]).spawn(trees)
    for t, child in enumerate(children):
        g = np.random.Generator(np.random.PCG64(child))
        n = int(g.integers(lo, hi + 1))
        m = n - 1
        out["num_leaves"][t] = n
        out["split_feature"][t, :m] = g.integers(0, cols, m)
        out["threshold"][t, :m] = g.standard_normal(m)
        out["missing_type"][t, :m] = MISSING_NAN
        out["default_left"][t, :m] = g.integers(0, 2, m) == 1
        out["split_gain"][t, :m] = g.gamma(2.0, 5.0, m)
        out["leaf_value"][t, :n] = g.standard_normal(n) * scale
        which = (g.random(m) * np.arange(1, n)).astype(np.int64)
        share = g.uniform(0.1, 0.9, m)
        lc, rc = out["left_child"][t], out["right_child"][t]
        count = out["leaf_count"][t]
        count[0] = rows
        link = {0: None}                  # leaf -> (parent node, is left)
        for i in range(m):                # node i splits one of i + 1 leaves
            leaf = int(which[i])
            if link[leaf] is not None:
                node, is_left = link[leaf]
                (lc if is_left else rc)[node] = i
            lc[i], rc[i] = ~leaf, ~(i + 1)
            link[leaf], link[i + 1] = (i, True), (i, False)
            out["internal_count"][t, i] = count[leaf]
            left = int(count[leaf] * share[i])
            count[leaf], count[i + 1] = left, count[leaf] - left
    return out


def _line(key: str, values, fmt: str) -> str:
    return key + "=" + " ".join(fmt % v for v in values)


def _tree_text(f: Dict[str, np.ndarray], t: int) -> str:
    n = int(f["num_leaves"][t])
    m = n - 1
    lc, rc = f["left_child"][t, :m], f["right_child"][t, :m]
    lv, cnt = f["leaf_value"][t, :n], f["leaf_count"][t, :n]
    # a node's value is its children's, weighted by their rows; children
    # are numbered after their parent, so one pass from the last node up
    ival = np.zeros(m, np.float64)
    for i in range(m - 1, -1, -1):
        parts = [(lv[~c], cnt[~c]) if c < 0
                 else (ival[c], f["internal_count"][t, c])
                 for c in (int(lc[i]), int(rc[i]))]
        total = sum(w for _, w in parts)
        ival[i] = sum(v * w for v, w in parts) / max(total, 1)
    # decision_type as tree.h packs it: bit 0 categorical, bit 1 default
    # left, bits 2-3 the missing type
    dt = (f["categorical"][t, :m].astype(np.int64)
          | f["default_left"][t, :m].astype(np.int64) << 1
          | f["missing_type"][t, :m].astype(np.int64) << 2)
    icnt = f["internal_count"][t, :m]
    lines = [f"Tree={t}", f"num_leaves={n}", "num_cat=0",
             _line("split_feature", f["split_feature"][t, :m], "%d"),
             _line("split_gain", f["split_gain"][t, :m], "%g"),
             _line("threshold", f["threshold"][t, :m], "%.17g"),
             _line("decision_type", dt, "%d"),
             _line("left_child", lc, "%d"), _line("right_child", rc, "%d"),
             _line("leaf_value", lv, "%.17g"),
             _line("leaf_weight", cnt * _HESSIAN, "%.17g"),
             _line("leaf_count", cnt, "%d"),
             _line("internal_value", ival, "%g"),
             _line("internal_weight", icnt * _HESSIAN, "%g"),
             _line("internal_count", icnt, "%d"),
             "is_linear=0", "shrinkage=0.1", "", ""]
    return "\n".join(lines)


def model_text(forest: Dict[str, np.ndarray], cols: int) -> str:
    """The forest as a LightGBM model file's text: header, one block a
    tree, the closing lines."""
    blocks: List[str] = [_tree_text(forest, t)
                         for t in range(len(forest["num_leaves"]))]
    head = ["tree", "version=v3", "num_class=1",
            "num_tree_per_iteration=1", "label_index=0",
            f"max_feature_idx={cols - 1}", "objective=binary sigmoid:1",
            "feature_names=" + " ".join(f"Column_{i}" for i in range(cols)),
            "feature_infos=" + " ".join(["[-6:6]"] * cols),
            _line("tree_sizes", [len(b) for b in blocks], "%d"), "", ""]
    tail = ["end of trees", "", "feature_importances:", "",
            "parameters:", "[boosting: gbdt]", "[objective: binary]",
            "end of parameters", "", "pandas_categorical:null", ""]
    return "\n".join(head) + "".join(blocks) + "\n".join(tail)
