"""What decides ``correct`` for a ranking cell, beside compare.py (whose
``numbers`` and ``judge`` it uses for the trees and the training
scores): the edges held over the columns the generator states as
continuous, the held-out scores, the lambdas and the in-scan NDCG.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import numpy as np

import compare

def edge_numbers(edges: Sequence[np.ndarray], bin_count: np.ndarray,
                 rows: int, max_bin: int, continuous: Sequence[int]
                 ) -> Dict[str, float]:
    """compare.edge_numbers with ``bin_mass_gap`` over the continuous
    columns only: a zero-inflated or small-integer column's heaviest bin
    is the data's, not the binning's."""
    cols = np.asarray(continuous, np.int64)
    return compare.edge_numbers(edges, bin_count[cols], rows, max_bin)


def tree_view(ref: Dict[str, Any], scores: np.ndarray, bin_mismatch: int,
              edges: Dict[str, float]) -> Dict[str, Any]:
    """compare.program_view without the log-loss (grades, not classes)."""
    trees = []
    for rec in ref["trees"]:
        tt = rec["tables"]
        trees.append({
            "split_at": np.stack([tt.feat, tt.thr_bin], axis=1)
            if tt.n_int else np.zeros((0, 2), np.int64),
            "leaf_value": tt.prog_lval, "leaf_count": tt.prog_lcount,
            "node_count": tt.prog_icount})
    return {"trees": trees, "scores": scores, "loss": 1.0,
            "bin_mismatch": int(bin_mismatch), "edges": edges}


def control_view(low: Dict[str, Any]) -> Dict[str, Any]:
    trees = []
    for rec in low["trees"]:
        tt = rec["tables"]
        split_at = rec["best_at"] if "best_at" in rec else np.stack(
            [tt.feat, tt.thr_bin], axis=1)
        trees.append({"split_at": split_at, "leaf_value": rec["leaf_value"],
                      "leaf_count": rec["leaf_count"],
                      "node_count": rec["node_count"]})
    return {"trees": trees, "scores": np.asarray(low["score"]), "loss": 1.0,
            "bin_mismatch": 0,
            "edges": {"bin_edges_bad": 0.0, "bin_mass_gap": 0.0}}


def _widest_over_rms(got, ref) -> float:
    r = np.asarray(ref, np.float64)
    g = np.asarray(got, np.float64)
    if g.shape != r.shape or not np.all(np.isfinite(g)):
        return compare.MISSING
    return float(np.max(np.abs(g - r))) / max(
        float(np.sqrt(np.mean(r * r))), 1e-30)


def lambda_gap(pairs) -> float:
    """``pairs``: [(program's array, reference's array)] over the lambdas
    and hessians at each set of scores; the widest gap over the
    reference's root mean square, worst array."""
    return max(_widest_over_rms(g, r) for g, r in pairs)


def numbers(ref: Dict[str, Any], view: Dict[str, Any], rows: int,
            held_scores: Optional[np.ndarray], grads, ndcg: np.ndarray
            ) -> Dict[str, float]:
    """``ref``: RankFollower.follow_rank's result. ``view``: tree_view or
    control_view. ``held_scores``: the view's held-out scores after the
    chunk. ``grads``: lambda_gap's pairs. ``ndcg`` [trees, k]: the view's
    NDCG after every tree."""
    base = dict(ref, scores=[ref["score"]], loss_after=1.0)
    nums = compare.numbers(base, view, rows)
    nums.pop("loss_gap")
    sref = np.asarray(ref["held_score"], np.float64)
    moved = float(np.sqrt(np.mean(sref ** 2)))
    if held_scores is None or np.shape(held_scores) != sref.shape:
        nums["heldout_score_gap"] = compare.MISSING
    else:
        nums["heldout_score_gap"] = float(np.max(np.abs(
            np.asarray(held_scores, np.float64) - sref))) / max(moved, 1e-30)
    nums["lambda_gap"] = lambda_gap(grads)
    want = np.asarray(ref["ndcg"], np.float64)
    got = np.asarray(ndcg, np.float64)
    nums["ndcg_gap"] = float(np.max(np.abs(got - want))) \
        if got.shape == want.shape and np.all(np.isfinite(got)) \
        else compare.MISSING
    return nums
