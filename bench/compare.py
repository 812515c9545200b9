"""The comparison that decides ``correct`` for a training cell.

A *view* is what a trainer produced, in the reference's terms: for each
followed tree the split it chose at every internal node, its leaf values
and counts; the scores and log-loss after those trees; and how many
binned cells differ from the reference's own binning. ``program_view``
builds it from the timed path's drained trees and device scores;
``control_view`` builds it from the reference run in a lower operand
precision. ``numbers`` measures a view against the float32 reference;
``judge`` holds each number to its limit.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

MISSING = 1.0e300     # a number that could not be taken: over any limit

NAMES = ("bin_mismatch", "split_gain_gap", "leaf_value_gap", "count_gap",
         "score_gap", "loss_gap")


def logloss(scores: np.ndarray, y: np.ndarray) -> float:
    s = scores.astype(np.float64)
    return float(np.mean(np.logaddexp(0.0, s) - y * s))


def program_view(ref: Dict[str, Any], scores: np.ndarray, y: np.ndarray,
                 bin_mismatch: int) -> Dict[str, Any]:
    """From the drained trees (already parsed into the reference's
    ``TreeTables``) and the device scores after those trees."""
    trees = []
    for rec in ref["trees"]:
        tt = rec["tables"]
        trees.append({
            "split_at": np.stack([tt.feat, tt.thr_bin], axis=1)
            if tt.n_int else np.zeros((0, 2), np.int64),
            "leaf_value": tt.prog_lval, "leaf_count": tt.prog_lcount,
            "node_count": tt.prog_icount})
    return {"trees": trees, "scores": scores, "loss": logloss(scores, y),
            "bin_mismatch": int(bin_mismatch)}


def control_view(low: Dict[str, Any], rows: int) -> Dict[str, Any]:
    trees = []
    for rec in low["trees"]:
        tt = rec["tables"]
        split_at = rec["best_at"] if "best_at" in rec else np.stack(
            [tt.feat, tt.thr_bin], axis=1)
        trees.append({"split_at": split_at,
                      "leaf_value": rec["leaf_value"],
                      "leaf_count": rec["leaf_count"],
                      "node_count": rec["node_count"]})
    return {"trees": trees, "scores": host_scores(low, rows),
            "loss": low["loss_after"], "bin_mismatch": 0}


def host_scores(followed: Dict[str, Any], rows: int) -> np.ndarray:
    return np.concatenate(
        [np.asarray(s) for s in followed["scores"]])[:rows]


def numbers(ref: Dict[str, Any], view: Dict[str, Any], rows: int
            ) -> Dict[str, float]:
    """Each number compared, a view against the float32 reference."""
    gain_gap = 0.0
    leaf_gap = 0.0
    count_gap = 0.0
    if len(view["trees"]) != len(ref["trees"]) or not ref["trees"]:
        return {n: MISSING for n in NAMES}
    for rec, vt in zip(ref["trees"], view["trees"]):
        step = np.abs(rec["leaf_step"])
        scale = np.maximum(step, np.median(step))
        d = np.abs((vt["leaf_value"] - rec["bias"]) - rec["leaf_step"])
        leaf_gap = max(leaf_gap, float(np.max(d / scale)))
        cref = np.concatenate([rec["leaf_count"], rec["node_count"]])
        cview = np.concatenate([vt["leaf_count"], vt["node_count"]])
        cscale = np.maximum(cref, np.median(rec["leaf_count"]))
        count_gap = max(count_gap,
                        float(np.max(np.abs(cview - cref) / cscale)))
        if "gain_table" in rec:
            at = vt["split_at"]
            j = np.arange(len(at))
            got = rec["gain_table"][j, at[:, 0], at[:, 1]]
            best = rec["gain_best"]
            ok = best > 0
            gap = np.where(ok, (best - got) / np.where(ok, best, 1.0), 0.0)
            gain_gap = max(gain_gap, float(np.max(gap, initial=0.0)))
    sref = host_scores(ref, rows).astype(np.float64)
    sview = np.asarray(view["scores"], np.float64)
    moved = float(np.sqrt(np.mean((sref - ref["init"]) ** 2)))
    dev = np.abs(sview - sref)
    score_gap = float(np.max(dev)) / max(moved, 1e-30)
    view["rows_off"] = {f"over_{k:g}_moves": int(np.sum(dev > k * moved))
                        for k in (0.01, 0.1, 1.0, 10.0)}
    loss_gap = abs(view["loss"] - ref["loss_after"]) / ref["loss_after"]
    return {"bin_mismatch": float(view["bin_mismatch"]),
            "split_gain_gap": gain_gap, "leaf_value_gap": leaf_gap,
            "count_gap": count_gap, "score_gap": score_gap,
            "loss_gap": float(loss_gap)}


def judge(nums: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, Any]:
    """``{"correct": bool, "checks": {name: {"value", "limit", "ok"}}}``.
    Every limit in the cell's file has to be met by a finite number."""
    checks = {}
    ok_all = bool(limits)
    for name, limit in limits.items():
        v = nums.get(name, float("inf"))
        ok = bool(np.isfinite(v) and v <= limit)
        checks[name] = {"value": v, "limit": limit, "ok": ok}
        ok_all = ok_all and ok
    return {"correct": ok_all, "checks": checks}


def score_numbers(ref: np.ndarray, got: Optional[np.ndarray],
                  passes_differ: int) -> Dict[str, float]:
    """Scored margins against the reference's: the widest gap over all
    rows as a share of the reference margins' root mean square, the share
    of rows that are off by more than a thousandth of it, rows with no
    (finite) answer, and passes of the window that differ from the last."""
    if got is None or got.shape != ref.shape:
        return {"margin_gap": MISSING, "rows_off_share": MISSING,
                "rows_unanswered": MISSING,
                "passes_differ": float(passes_differ)}
    r = ref.astype(np.float64)
    g = np.asarray(got, np.float64)
    bad = ~np.isfinite(g)
    rms = float(np.sqrt(np.mean(r * r)))
    d = np.abs(np.where(bad, 0.0, g) - r) / max(rms, 1e-30)
    return {"margin_gap": float(d.max()),
            "rows_off_share": float(np.mean(d > 1e-3)),
            "rows_unanswered": float(bad.sum()),
            "passes_differ": float(passes_differ)}
