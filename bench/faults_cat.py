"""Planted faults of the categorical training cell, and how to read them
at the cell's own size on the chip:

    python3 bench/faults_cat.py --workload <cell> --fault BitFlipped --seeds 1,2,3
    python3 bench/faults_cat.py --workload <cell> --fault LeafOff,HalfTheBatch --seeds 4

Each is a ``tamper`` of kinds/train_cat.py: a run with the fault
underneath has to come out not correct, by one of the cell's limits.
One JSON line a seed. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import sys

import numpy as np


def _nodes(node):
    if "split_index" in node:
        yield node
        yield from _nodes(node["left_child"])
        yield from _nodes(node["right_child"])


def _leaves(node):
    if "split_index" not in node:
        yield node
    else:
        yield from _leaves(node["left_child"])
        yield from _leaves(node["right_child"])


class BitFlipped:
    """One bit of one node's bitset flipped: the first tree's heaviest
    categorical node with two categories or more drops the first of them
    from its left set (the model read back is not the model the scores
    came from)."""

    def trees(self, trees):
        out = copy.deepcopy(trees)
        for node in sorted(_nodes(out[0]["tree_structure"]),
                           key=lambda n: -n["internal_count"]):
            cats = str(node["threshold"]).split("||")
            if node["decision_type"] == "==" and len(cats) > 1:
                node["threshold"] = "||".join(cats[1:])
                return out
        raise ValueError("no categorical node of two categories in the "
                         "first tree")


class LeafOff:
    """One leaf of the first chunk's second tree 1 % off (the biggest)."""

    def trees(self, trees):
        out = copy.deepcopy(trees)
        leaf = max(_leaves(out[min(1, len(out) - 1)]["tree_structure"]),
                   key=lambda n: n.get("leaf_count", 0))
        leaf["leaf_value"] *= 1.01
        return out


class StaleAuc:
    """The held-out AUC read off the scores of the tree before: every row
    of a chunk's AUC values is the one above it, the first the AUC of the
    starting scores (all equal: 0.5)."""

    def metrics(self, vals):
        return np.concatenate([np.full_like(vals[:1], 0.5), vals[:-1]])


class StateUnchanged:
    """A step that returns its state unchanged: trees are grown, the
    training scores stay where they were."""

    def after_chunk(self, booster, before):
        booster._gbdt.scores = before


class HalfTheBatch:
    """Half of the rows left out of every sum: the in-bag mask the scan
    takes is zero on the second half (its histograms, its counts)."""

    def after_init(self, booster):
        import jax.numpy as jnp
        g = booster._gbdt
        n = g._host_pad
        half = jnp.concatenate(
            [jnp.ones((n // 2,), jnp.float32),
             jnp.zeros((n - n // 2,), jnp.float32)])
        # whichever of the two the batched scan takes
        g._in_bag_ones = g._in_bag_dev = half


class CoarseBins:
    """Ingest at the next narrower kernel width: the program is handed
    ``max_bin`` 31 where the configuration states 63, so every bin of a
    numeric column holds twice its share of the rows."""

    def params(self, params):
        return dict(params, max_bin=31)


FAULTS = {c.__name__: c for c in (BitFlipped, LeafOff, StaleAuc,
                                  StateUnchanged, HalfTheBatch, CoarseBins)}


def main() -> int:
    import run as harness
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True,
                    help="one of %s, or several joined by commas"
                         % ", ".join(sorted(FAULTS)))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.1)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--overrides", default=None)
    args = ap.parse_args()
    ov = json.loads(args.overrides) if args.overrides else None
    faults = args.fault.split(",")
    unknown = sorted(set(faults) - set(FAULTS))
    if unknown:
        ap.error(f"no fault {unknown}")
    for fault in faults:
        for seed in (int(s) for s in args.seeds.split(",")):
            res = harness.run_cell(args.workload, seed, args.seconds, False,
                                   require_chip=not args.allow_cpu,
                                   tamper=FAULTS[fault](), overrides=ov)
            print(json.dumps({"seed": seed, "fault": fault,
                              "correct": res["correct"],
                              "numbers": res["numbers"],
                              "compared": res["compared"]}), flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
