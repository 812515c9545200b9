"""What decides ``correct`` for the categorical training cell, beside
compare.py (whose ``numbers`` and ``judge`` it uses for the leaves, the
counts, the training scores and the log-loss): the category map, the
edges of the numeric columns, the split choice of the judged trees with
categorical splits among them, the held-out scores and the in-scan AUC.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

import compare


def splits_of(tables) -> List[Any]:
    """A followed tree's splits in gain_of's terms."""
    out = []
    for j in range(tables.n_int):
        f = int(tables.feat[j])
        if tables.is_cat[j]:
            out.append((f, "cat", list(np.flatnonzero(tables.left[j]))))
        else:
            out.append((f, "num", int(tables.thr_bin[j])))
    return out


def tree_view(ref: Dict[str, Any], scores: np.ndarray, y: np.ndarray,
              bin_mismatch: int, map_nums: Dict[str, float]
              ) -> Dict[str, Any]:
    """The program's view: its drained trees (parsed into the reference's
    tables), its device scores after them, ingest's map and edges."""
    trees = []
    for rec in ref["trees"]:
        tt = rec["tables"]
        trees.append({"splits": splits_of(tt), "leaf_value": tt.prog_lval,
                      "leaf_count": tt.prog_lcount,
                      "node_count": tt.prog_icount})
    return {"trees": trees, "scores": scores,
            "loss": compare.logloss(scores, y),
            "bin_mismatch": int(bin_mismatch), "maps": map_nums}


def control_view(low: Dict[str, Any], rows: int) -> Dict[str, Any]:
    """The reference run one precision below, in the program's place."""
    trees = []
    for rec in low["trees"]:
        tt = rec["tables"]
        trees.append({"splits": rec.get("best_split") or splits_of(tt),
                      "leaf_value": rec["leaf_value"],
                      "leaf_count": rec["leaf_count"],
                      "node_count": rec["node_count"]})
    return {"trees": trees, "scores": compare.host_scores(low, rows),
            "loss": low["loss_after"], "bin_mismatch": 0,
            "maps": {"cat_map_mismatch": 0.0, "bin_edges_bad": 0.0,
                     "bin_mass_gap": 0.0},
            "held_score": low.get("held_score"), "auc": low.get("auc")}


def split_gain_gap(fol, ref: Dict[str, Any], view: Dict[str, Any]) -> float:
    """Worst share of the reference's best gain that a chosen split loses,
    over the judged trees (numeric and categorical splits alike)."""
    gap = 0.0
    for rec, vt in zip(ref["trees"], view["trees"]):
        if "gain_best" not in rec:
            continue
        for j, split in enumerate(vt["splits"]):
            if split is None:
                continue
            best = rec["gain_best"][j]
            if best > 0:
                got = fol.gain_of(rec, j, split)
                gap = max(gap, (best - got) / best)
    return float(gap)


def numbers(fol, ref: Dict[str, Any], view: Dict[str, Any], rows: int,
            held_scores: Optional[np.ndarray], aucs) -> Dict[str, float]:
    """Each number compared, a view against the float32 reference.
    ``held_scores``: the view's held-out scores after the chunk; ``aucs``
    [trees]: the view's held-out AUC after every tree."""
    base_view = {"trees": [
        {"split_at": np.zeros((0, 2), np.int64), **{k: t[k] for k in (
            "leaf_value", "leaf_count", "node_count")}}
        for t in view["trees"]], "scores": view["scores"],
        "loss": view["loss"], "bin_mismatch": view["bin_mismatch"],
        "edges": {}}
    nums = compare.numbers(ref, base_view, rows)
    view["rows_off"] = base_view.get("rows_off")
    if len(view["trees"]) != len(ref["trees"]):
        nums["split_gain_gap"] = compare.MISSING
    else:
        nums["split_gain_gap"] = split_gain_gap(fol, ref, view)
    nums.update(view["maps"])
    sref = np.asarray(ref.get("held_score"), np.float64)
    moved = float(np.sqrt(np.mean((sref - ref["init"]) ** 2)))
    if held_scores is None or np.shape(held_scores) != sref.shape:
        nums["heldout_score_gap"] = compare.MISSING
    else:
        nums["heldout_score_gap"] = float(np.max(np.abs(
            np.asarray(held_scores, np.float64) - sref))) / max(moved, 1e-30)
    want = np.asarray(ref.get("auc"), np.float64)
    got = np.asarray(aucs, np.float64).reshape(-1)
    nums["auc_gap"] = float(np.max(np.abs(got - want))) \
        if got.shape == want.shape and np.all(np.isfinite(got)) \
        else compare.MISSING
    return nums
