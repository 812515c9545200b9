"""Planted faults of a ranking cell, and how to read them at the cell's
own size on the chip:

    python3 bench/faults_rank.py --workload <cell> --fault StaleNdcg --seeds 1,2,3

Each is a ``tamper`` of kinds/train_rank.py: a run with the fault
underneath has to come out not correct, by one of the cell's limits.
One JSON line a seed. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import numpy as np


class StaleNdcg:
    """The metric reads the scores of the tree before: every row of a
    chunk's NDCG values is the row above it (the first stays)."""

    def metrics(self, vals):
        return np.concatenate([vals[:1], vals[:-1]])


class TruncationOff:
    """Pairs past rank 30 counted: the program is handed a truncation
    level no query reaches, where the configuration states 30."""

    def params(self, params):
        return dict(params, lambdarank_truncation_level=1 << 20)


class GroupShift:
    """Held-out query boundaries off by one row: the first query takes
    the second's first row, and so on down the table."""

    def heldout_group(self, lengths):
        out = np.array(lengths, np.int64)
        last = int(np.flatnonzero(out > 1)[-1])
        out[0] += 1
        out[last] -= 1
        return out


FAULTS = {c.__name__: c for c in (StaleNdcg, TruncationOff, GroupShift)}


def main() -> int:
    import run as harness
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.1)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--overrides", default=None)
    args = ap.parse_args()
    ov = json.loads(args.overrides) if args.overrides else None
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(args.workload, seed, args.seconds, False,
                               require_chip=not args.allow_cpu,
                               tamper=FAULTS[args.fault](), overrides=ov)
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": res["correct"],
                          "numbers": res["numbers"],
                          "compared": res["compared"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
