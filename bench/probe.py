"""Read the numbers that decide ``correct`` on several seeds in one
process, the program's and the control's (the reference in the operand
precision the configuration names as its control). This is how the
limits in a cell's file are set and re-read on the chip:

    python3 bench/probe.py --workload <cell> --seeds 1,2,3 --control-seeds 1,2

Each seed makes its data, ingests, runs the window's own call once and a
window of one chunk, and is compared at the cell's own size. One JSON
line per seed on standard output. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import run as harness


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.1)
    ap.add_argument("--control-key", default="control_operand_dtype",
                    help="the configuration's key that names its control "
                         "(control_feature_terms for a score cell)")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--detail", default=None, metavar="PATH",
                    help="write per-node and per-leaf readings as JSON")
    ap.add_argument("--overrides", default=None,
                    help="JSON merged into the cell and configuration")
    args = ap.parse_args()
    control = {int(s) for s in args.control_seeds.split(",") if s}
    ov = json.loads(args.overrides) if args.overrides else None
    cfg = harness.load_cell(args.workload)["config"]
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(
            args.workload, seed, args.seconds, False,
            require_chip=not args.allow_cpu, overrides=ov,
            detail=bool(args.detail),
            control_dtype=str(cfg[args.control_key])
            if seed in control else None)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "numbers": res["numbers"],
                          "control": res.get("control"),
                          "metrics": res["metrics"], "info": res["info"],
                          "device": res["device"]}), flush=True)
        if args.detail:
            with open(f"{args.detail}.{seed}.json", "w") as f:
                json.dump(res.get("detail"), f)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
