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

NAMES = ("bin_edges_bad", "bin_mass_gap", "bin_mismatch", "split_gain_gap",
         "leaf_value_gap", "count_gap", "score_gap", "loss_gap")


def logloss(scores: np.ndarray, y: np.ndarray) -> float:
    s = scores.astype(np.float64)
    return float(np.mean(np.logaddexp(0.0, s) - y * s))


def edge_numbers(edges: Sequence[np.ndarray], bin_count: np.ndarray,
                 rows: int, max_bin: int) -> Dict[str, float]:
    """``edges``: per column the inclusive upper bounds, the last +inf.
    ``bin_count`` [F, B]: the raw rows of each column that the reference
    counted into each bin. ``bin_edges_bad``: columns with more than
    ``max_bin`` bins, a bound that does not rise, or a last bound that is
    not +inf. ``bin_mass_gap``: the heaviest bin's share of the rows times
    ``max_bin``, less 1, worst column (0 where every column's ``max_bin``
    bins hold the same; 1 where half as many bins do)."""
    bad = sum(1 for e in edges
              if not 1 <= len(e) <= max_bin or np.any(np.diff(e) <= 0)
              or not np.isposinf(e[-1]))
    heaviest = float(np.max(bin_count)) / max(rows, 1)
    return {"bin_edges_bad": float(bad),
            "bin_mass_gap": heaviest * max_bin - 1.0}


def program_view(ref: Dict[str, Any], scores: np.ndarray, y: np.ndarray,
                 bin_mismatch: int, edges: Dict[str, float]
                 ) -> Dict[str, Any]:
    """From the drained trees (already parsed into the reference's
    ``TreeTables``), the device scores after those trees and ingest's
    edges as ``edge_numbers`` read them."""
    trees = []
    for rec in ref["trees"]:
        tt = rec["tables"]
        trees.append({
            "split_at": np.stack([tt.feat, tt.thr_bin], axis=1)
            if tt.n_int else np.zeros((0, 2), np.int64),
            "leaf_value": tt.prog_lval, "leaf_count": tt.prog_lcount,
            "node_count": tt.prog_icount})
    return {"trees": trees, "scores": scores, "loss": logloss(scores, y),
            "bin_mismatch": int(bin_mismatch), "edges": edges}


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
    # the control rounds operands, not bins: it bins as the reference
    return {"trees": trees, "scores": host_scores(low, rows),
            "loss": low["loss_after"], "bin_mismatch": 0,
            "edges": {"bin_edges_bad": 0.0, "bin_mass_gap": 0.0}}


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
    return {**view["edges"], "bin_mismatch": float(view["bin_mismatch"]),
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
