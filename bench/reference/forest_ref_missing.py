"""Plain reference for scoring rows with missing values against a forest
of decision trees, as LightGBM's ``Tree::NumericalDecision`` (tree.h)
decides each node.

It imports nothing of the program under test, and it walks the forest's
arrays as their maker wrote them (bench/forestgen.py ``make``; ``from_dump``
brings a dumped model into the same arrays): per node ``split_feature``,
``threshold`` (float64), ``missing_type`` (0 None, 1 Zero, 2 NaN),
``default_left``, ``left_child`` / ``right_child`` (``~leaf`` for a
leaf); per leaf ``leaf_value``. For a row's float32 value v at a node:

    if v is NaN and the node's type is not NaN:  v = 0.0
    if (type is Zero and |v| <= 1e-35) or (type is NaN and v is NaN):
        go the default direction
    else:
        go left when v <= threshold

Thresholds are float64 in the model; for a float32 v, v <= b exactly when
v <= floor32(b), so the walk compares in float32 against the floored
threshold. The margin is the sum of the leaves a row reaches, added tree
by tree in float32. The walk is written with the tree's path matrix
(decisions +1 left, -1 right, times path signs, equals the path length
only at the row's leaf), in row blocks so that it runs beside the table
on whatever device JAX has, at ``default_matmul_precision("highest")``.
Feature values are gathered (``jnp.take``), never multiplied, so they
keep every bit. A categorical node raises.

``feature_terms=2`` is the control of the precision below the stated one
(each feature value and leaf value as the sum of two bfloat16 terms, as
in forest_ref.py). ``nan_as_zero=True`` is the planted fault: a NaN is
read as 0.0 at every node, whatever its type.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from reference.gbdt_ref import floor32, round_to, _round_up

_NO_LEAF = 1.0e6
_KZERO = 1e-35
_TYPES = {"None": 0, "Zero": 1, "NaN": 2}
_KEYS = ("feat", "thr", "mtype", "dleft", "P", "plen", "lv")


def from_dump(trees: Sequence[Dict[str, Any]]) -> Dict[str, np.ndarray]:
    """A dumped model's ``tree_info`` as the arrays ``score`` walks."""
    parsed = []
    for tree in trees:
        nodes, leaves = {}, {}
        stack = [tree["tree_structure"]]
        while stack:
            node = stack.pop()
            if "split_index" not in node:
                leaves[int(node.get("leaf_index", 0))] = node
                continue
            nodes[int(node["split_index"])] = node
            stack += [node["left_child"], node["right_child"]]
        parsed.append((nodes, leaves))
    T = len(parsed)
    J = max(max(len(p[0]) for p in parsed), 1)
    L = max(len(p[1]) for p in parsed)
    out = {"num_leaves": np.array([len(p[1]) for p in parsed], np.int32),
           "split_feature": np.zeros((T, J), np.int32),
           "threshold": np.zeros((T, J), np.float64),
           "missing_type": np.zeros((T, J), np.int8),
           "default_left": np.zeros((T, J), bool),
           "categorical": np.zeros((T, J), bool),
           "left_child": np.zeros((T, J), np.int32),
           "right_child": np.zeros((T, J), np.int32),
           "leaf_value": np.zeros((T, L), np.float64),
           "leaf_count": np.ones((T, L), np.int64)}

    def ref(child: Dict[str, Any]) -> int:
        return int(child["split_index"]) if "split_index" in child \
            else ~int(child.get("leaf_index", 0))

    for t, (nodes, leaves) in enumerate(parsed):
        for j, node in nodes.items():
            out["split_feature"][t, j] = node["split_feature"]
            out["categorical"][t, j] = node.get("decision_type",
                                                "<=") != "<="
            if not out["categorical"][t, j]:
                out["threshold"][t, j] = node["threshold"]
            out["missing_type"][t, j] = _TYPES[node.get("missing_type",
                                                        "None")]
            out["default_left"][t, j] = bool(node.get("default_left"))
            out["left_child"][t, j] = ref(node["left_child"])
            out["right_child"][t, j] = ref(node["right_child"])
        for li, leaf in leaves.items():
            out["leaf_value"][t, li] = leaf["leaf_value"]
            out["leaf_count"][t, li] = leaf.get("leaf_count", 1) or 1
    return out


def _paths(forest: Dict[str, np.ndarray], t: int):
    """[(leaf, [(node, +1 left | -1 right), ...])] of tree t."""
    n = int(forest["num_leaves"][t])
    if n < 2:
        return [(0, [])]
    out, stack = [], [(0, [])]
    while stack:
        node, path = stack.pop()
        for child, sign in ((int(forest["left_child"][t, node]), 1.0),
                            (int(forest["right_child"][t, node]), -1.0)):
            step = path + [(node, sign)]
            if child < 0:
                out.append((~child, step))
            else:
                stack.append((child, step))
    return out


def forest_tables(forest: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The forest's arrays as the walk takes them: per node [T, J] split
    feature, floored threshold, missing type and default direction;
    [T, L, J] path signs; [T, L] path length and leaf value. J and L are
    padded (a padded node is on no path, a padded leaf is never hit)."""
    if forest["categorical"].any():
        raise ValueError("reference walks numeric splits only")
    T, J0 = forest["split_feature"].shape
    J, L = _round_up(J0, 8), _round_up(forest["leaf_value"].shape[1], 8)
    out = {"feat": np.zeros((T, J), np.int32),
           "thr": np.full((T, J), np.inf, np.float32),
           "mtype": np.zeros((T, J), np.int32),
           "dleft": np.zeros((T, J), bool),
           "P": np.zeros((T, L, J), np.float32),
           "plen": np.full((T, L), _NO_LEAF, np.float32),
           "lv": np.zeros((T, L), np.float32)}
    out["feat"][:, :J0] = forest["split_feature"]
    out["thr"][:, :J0] = floor32(forest["threshold"])
    out["mtype"][:, :J0] = forest["missing_type"]
    out["dleft"][:, :J0] = forest["default_left"]
    for t in range(T):
        m = max(int(forest["num_leaves"][t]) - 1, 0)
        out["thr"][t, m:] = np.inf
        for leaf, path in _paths(forest, t):
            out["plen"][t, leaf] = float(len(path))
            out["lv"][t, leaf] = np.float32(forest["leaf_value"][t, leaf])
            for node, sign in path:
                out["P"][t, leaf, node] = sign
    return out


def _two_terms(v):
    """v as the sum of two bfloat16 terms: 16 bits of mantissa."""
    hi = round_to(v, "bfloat16")
    return hi + round_to(v - hi, "bfloat16")


@functools.partial(jax.jit, static_argnames=("feature_terms", "nan_as_zero"))
def _score_block(x, feat, thr, mtype, dleft, P, plen, lv, *,
                 feature_terms: int, nan_as_zero: bool):
    """x [R, F] float32 -> margins [R] float32."""
    xt = x.T                                                # [F, R]
    if feature_terms == 2:
        xt = _two_terms(xt)            # NaN stays NaN
        lv = _two_terms(lv)

    def per_tree(acc, tab):
        f_t, thr_t, mt_t, dl_t, P_t, plen_t, lv_t = tab
        v = jnp.take(xt, f_t, axis=0)                       # [J, R]
        nan = jnp.isnan(v)
        mt = mt_t[:, None]
        if nan_as_zero:
            v, nan = jnp.where(nan, 0.0, v), jnp.zeros_like(nan)
        v = jnp.where(nan & (mt != 2), 0.0, v)
        missing = ((mt == 1) & (jnp.abs(v) <= _KZERO)) | ((mt == 2) & nan)
        left = jnp.where(missing, dl_t[:, None], v <= thr_t[:, None])
        d = jnp.where(left, 1.0, -1.0).astype(jnp.bfloat16)
        s = jax.lax.dot_general(P_t.astype(jnp.bfloat16), d,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        hit = s == plen_t[:, None]                          # [L, R]
        val = jnp.sum(jnp.where(hit, lv_t[:, None], 0.0), axis=0)
        return acc + val, None

    acc, _ = jax.lax.scan(per_tree, jnp.zeros((x.shape[0],), jnp.float32),
                          (feat, thr, mtype, dleft, P, plen, lv))
    return acc


def score(X: np.ndarray, forest: Dict[str, np.ndarray],
          block: int = 1 << 17, feature_terms: int = 0,
          nan_as_zero: bool = False) -> np.ndarray:
    """Margins [N] float32 of the rows of X under the forest's arrays."""
    tabs = forest_tables(forest)
    n = X.shape[0]
    out = np.empty(n, np.float32)
    with jax.default_matmul_precision("highest"):
        dev = [jnp.asarray(tabs[k]) for k in _KEYS]
        for lo in range(0, n, block):
            hi = min(lo + block, n)
            xb = X[lo:hi]
            if hi - lo < block and n > block:
                xb = np.concatenate([xb, np.zeros(
                    (block - (hi - lo), X.shape[1]), np.float32)])
            out[lo:hi] = np.asarray(_score_block(
                jnp.asarray(xb), *dev, feature_terms=feature_terms,
                nan_as_zero=nan_as_zero))[:hi - lo]
    return out


def forest_facts(forest: Dict[str, np.ndarray]) -> Dict[str, float]:
    """What the forest is, read from its arrays: trees, the most leaves
    of one, its nodes of each missing type, and the comparisons one row
    needs (the sum over trees of the mean path length, leaves weighted by
    the rows they hold)."""
    live = np.arange(forest["split_feature"].shape[1])[None, :] \
        < (forest["num_leaves"][:, None] - 1)
    depth = 0.0
    for t in range(len(forest["num_leaves"])):
        w = forest["leaf_count"][t].astype(np.float64)
        paths = _paths(forest, t)
        den = sum(max(w[leaf], 1.0) for leaf, _ in paths)
        depth += sum(max(w[leaf], 1.0) * len(p) for leaf, p in paths) / den
    kinds = {k: int((live & (forest["missing_type"] == v)).sum())
             for k, v in _TYPES.items()}
    return {"forest_trees": len(forest["num_leaves"]),
            "forest_max_leaves": int(forest["num_leaves"].max()),
            "forest_nan_nodes": kinds["NaN"],
            "forest_zero_nodes": kinds["Zero"],
            "forest_none_nodes": kinds["None"],
            "forest_mean_depth": float(depth)}
