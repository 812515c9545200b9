"""Plain reference for scoring rows against a forest of decision trees.

It imports nothing of the program under test. A row walks each tree from
the root: at an internal node it goes left when its feature value is at
most the node's threshold (thresholds are float64 in the model; for a
float32 value v, v <= b exactly when v <= floor32(b)), and the margin is
the sum of the leaves it reaches, added tree by tree in float32. The walk
is written with the tree's path matrix, so that it is a few matrix
products over a block of rows and runs on whatever device JAX has:
decisions (+1 left, -1 right) times path signs equals the path length
only for the leaf the row reaches. Feature values are gathered, never
multiplied, so they keep every bit.

``feature_terms`` is the control: 0 keeps float32 values; 2 holds each
feature value and each leaf value as the sum of two bfloat16 terms, which
is what a three-pass ("high") product with an exact one-hot delivers in
place of the six-pass ("highest") one the configuration states.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from reference.gbdt_ref import floor32, round_to, _round_up

_NO_LEAF = 1.0e6


def forest_tables(trees: Sequence[Dict[str, Any]]) -> Dict[str, np.ndarray]:
    """Stack the dumped trees into arrays: [T, J] split feature and
    floored threshold, [T, L, J] path signs, [T, L] path length and leaf
    value. J and L are padded to the widest tree."""
    parsed = []
    for tree in trees:
        feat, thr, lval, paths = {}, {}, {}, {}
        stack = [(tree["tree_structure"], [])]
        while stack:
            node, path = stack.pop()
            if "split_index" not in node:
                li = int(node.get("leaf_index", 0))
                lval[li] = float(node["leaf_value"])
                paths[li] = path
                continue
            if node.get("decision_type", "<=") != "<=":
                raise ValueError("reference walks numeric splits only")
            j = int(node["split_index"])
            feat[j] = int(node["split_feature"])
            thr[j] = float(node["threshold"])
            stack.append((node["left_child"], path + [(j, 1.0)]))
            stack.append((node["right_child"], path + [(j, -1.0)]))
        parsed.append((feat, thr, lval, paths))
    J = _round_up(max(max(len(p[0]) for p in parsed), 1), 8)
    L = _round_up(max(len(p[2]) for p in parsed), 8)
    T = len(parsed)
    out = {"feat": np.zeros((T, J), np.int32),
           "thr": np.full((T, J), np.inf, np.float32),
           "P": np.zeros((T, L, J), np.float32),
           "plen": np.full((T, L), _NO_LEAF, np.float32),
           "lv": np.zeros((T, L), np.float32),
           "depth_sum": np.zeros(T)}
    for t, (feat, thr, lval, paths) in enumerate(parsed):
        for j in feat:
            out["feat"][t, j] = feat[j]
            out["thr"][t, j] = floor32(np.array([thr[j]]))[0]
        for li, path in paths.items():
            out["plen"][t, li] = float(len(path))
            out["lv"][t, li] = np.float32(lval[li])
            for j, sign in path:
                out["P"][t, li, j] = sign
    return out


def _two_terms(v):
    """v as the sum of two bfloat16 terms: 16 bits of mantissa."""
    hi = round_to(v, "bfloat16")
    return hi + round_to(v - hi, "bfloat16")


@functools.partial(jax.jit, static_argnames=("feature_terms",))
def _score_block(x, feat, thr, P, plen, lv, *, feature_terms: int):
    """x [R, F] float32 -> margins [R] float32."""
    xt = x.T                                                # [F, R]
    if feature_terms == 2:
        xt = _two_terms(xt)
        lv = _two_terms(lv)

    def per_tree(acc, tab):
        f_t, thr_t, P_t, plen_t, lv_t = tab
        cols = jnp.take(xt, f_t, axis=0)                    # [J, R]
        d = jnp.where(cols <= thr_t[:, None], 1.0, -1.0) \
            .astype(jnp.bfloat16)
        s = jax.lax.dot_general(P_t.astype(jnp.bfloat16), d,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        hit = s == plen_t[:, None]                          # [L, R]
        val = jnp.sum(jnp.where(hit, lv_t[:, None], 0.0), axis=0)
        return acc + val, None

    acc, _ = jax.lax.scan(per_tree, jnp.zeros((x.shape[0],), jnp.float32),
                          (feat, thr, P, plen, lv))
    return acc


def score(X: np.ndarray, trees: Sequence[Dict[str, Any]],
          block: int = 1 << 20, feature_terms: int = 0) -> np.ndarray:
    """Margins [N] float32 of the rows of X under the dumped trees."""
    tabs = forest_tables(trees)
    dev = [jnp.asarray(tabs[k]) for k in ("feat", "thr", "P", "plen", "lv")]
    n = X.shape[0]
    out = np.empty(n, np.float32)
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        xb = X[lo:hi]
        if hi - lo < block and n > block:
            xb = np.concatenate(
                [xb, np.zeros((block - (hi - lo), X.shape[1]), np.float32)])
        out[lo:hi] = np.asarray(_score_block(
            jnp.asarray(xb), *dev, feature_terms=feature_terms))[:hi - lo]
    return out


def mean_depth(trees: Sequence[Dict[str, Any]]) -> float:
    """Comparisons one row needs over the forest: the sum over trees of
    the mean path length, leaves weighted by the rows they held in
    training."""
    total = 0.0
    for tree in trees:
        num, den = 0.0, 0.0
        stack = [(tree["tree_structure"], 0)]
        while stack:
            node, d = stack.pop()
            if "split_index" not in node:
                c = float(node.get("leaf_count", 1) or 1)
                num += c * d
                den += c
                continue
            stack.append((node["left_child"], d + 1))
            stack.append((node["right_child"], d + 1))
        total += num / max(den, 1.0)
    return total
