"""Run one cell of BENCHMARK.json once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, one configuration or one per-layer
metric is a file found by its name (bench/README.md):

    BENCHMARK.json workloads[].name   -> bench/workloads/<cell>.json
    (a name not in the manifest       -> bench/heldout/<cell>.json)
    BENCHMARK.json configs[].file     -> the configuration as it is run
    <cell>.json "kind"                -> bench/kinds/<kind>.py  (run(ctx))
    <cell>.json "per_layer": [names]  -> bench/metrics/<name>.json
    <metric>.json "reader"            -> bench/readers/<reader>.py (read(ctx, params))

The last line of standard output is the result as one JSON object. The
numbers that decided ``correct`` are its last key and the last lines of
standard error, each beside its limit.
"""

from __future__ import annotations

import time
T0 = time.perf_counter()          # set-up is counted from here

import argparse
import importlib
import json
import os
import sys
from typing import Any, Dict, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> Dict[str, Any]:
    """The cell's manifest entry, its own file and its configuration."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    path = os.path.join(BENCH_DIR, "workloads", name + ".json")
    if entry is None:
        # a cell held out of the manifest (PERF.md, Open questions):
        # runnable by hand and by the tests, never by the driver
        path = os.path.join(BENCH_DIR, "heldout", name + ".json")
        if not os.path.isfile(path):
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = load_json(path)
    if entry is None:
        entry = {"name": name, "config": cell["config"],
                 "chips": cell["chips"]}
        cfg_file = os.path.join("bench", "configs", cell["config"] + ".json")
    else:
        cfg_file = next(c for c in manifest["configs"]
                        if c["name"] == entry["config"])["file"]
    cell.setdefault("chips", entry["chips"])
    config = load_json(os.path.join(ROOT, cfg_file))
    return {"manifest": manifest, "entry": entry, "cell": cell,
            "config": config}


def load_module(package: str, name: str):
    if BENCH_DIR not in sys.path:
        sys.path.insert(0, BENCH_DIR)
    return importlib.import_module(f"{package}.{name}")


def read_metric(name: str, rctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """A per-layer metric through its own reader; None where the reader
    finds nothing to read."""
    spec = load_json(os.path.join(BENCH_DIR, "metrics", name + ".json"))
    reader = load_module("readers", spec["reader"])
    value = reader.read(rctx, spec.get("params", {}))
    if value is None:
        return None
    return {"value": float(value), "unit": spec["unit"]}


def merge(into: Dict[str, Any], extra: Dict[str, Any]) -> None:
    for k, v in extra.items():
        if isinstance(v, dict) and isinstance(into.get(k), dict):
            merge(into[k], v)
        else:
            into[k] = v


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, tamper: Any = None,
             control_dtype: Optional[str] = None,
             keep_trace: Any = None, detail: bool = False,
             overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Drive one run and build the result object (not printed here).
    ``overrides`` ({"cell": {...}, "config": {...}}) is for the tests
    under bench/tests, which shrink a cell to what a CPU can hold."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    loaded = load_cell(workload)
    cell, config, manifest = (loaded["cell"], loaded["config"],
                              loaded["manifest"])
    merge(cell, (overrides or {}).get("cell", {}))
    merge(config, (overrides or {}).get("config", {}))
    # a configuration may name a path of the program by the program's own
    # environment switches; they are set before the program is imported
    for key, value in config.get("env", {}).items():
        os.environ[key] = str(value)
    kind = load_module("kinds", cell["kind"])
    out = kind.run({
        "cell": cell, "config": config, "seed": seed, "seconds": seconds,
        "trace": trace, "t0": T0, "require_chip": require_chip,
        "tamper": tamper, "control_dtype": control_dtype,
        "keep_trace": keep_trace, "detail": detail})
    facts, reduction, device = out["facts"], out["trace"], out["device"]
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
    metrics: Dict[str, Any] = {}
    if not trace:
        for name in cell["end_to_end"]:
            metrics[name] = {"value": float(facts[name]),
                             "unit": units.get(name) or cell["units"][name]}
    else:
        peaks = load_json(os.path.join(BENCH_DIR, "peaks.json"))
        if device["kind"] not in peaks and require_chip:
            raise RuntimeError(f"no peaks for device {device['kind']!r}")
        rctx = {"facts": facts, "trace": reduction, "cell": cell,
                "config": config, "peak": peaks.get(device["kind"])}
        for name in cell["per_layer"]:
            got = read_metric(name, rctx)
            if got is not None:
                metrics[name] = got
    dev = dict(device, memory_peak_bytes=facts["memory_peak_bytes"])
    result: Dict[str, Any] = {
        "correct": bool(out["verdict"]["correct"]),
        "attempted": int(out["attempted"]), "failed": int(out["failed"]),
        "metrics": metrics, "device": dev}
    if trace and reduction is not None:
        dev["busy_s"] = reduction.busy_s
        dev["window_s"] = reduction.window_s
        result["breakdown"] = {"device_ops": reduction.top_ops(10),
                               "idle_gaps": reduction.top_gaps(5)}
    result["info"] = {k: facts[k] for k in (
        "datagen_s", "dataset_construct_s", "booster_init_s", "warmup_s",
        "setup_compiles", "compiles_in_window", "window_s",
        "trees_in_window", "reference_s") if k in facts}
    if "control_numbers" in facts:
        result["control"] = facts["control_numbers"]
    result["compared"] = {k: [c["value"], c["limit"]]
                          for k, c in out["verdict"]["checks"].items()}
    result["numbers"] = facts.get("numbers", {})
    if detail:
        result["detail"] = facts.get("detail")
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None, metavar="PATH",
                    help="with --trace 1: also write the trace's device "
                         "and host planes as JSON (how a fixture is made)")
    args = ap.parse_args()
    keep = None
    if args.dump_trace:
        def keep(raw: Dict[str, Any]) -> None:
            with open(args.dump_trace, "w") as f:
                json.dump(raw, f)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), keep_trace=keep)
    compared = result.pop("compared")
    numbers = result.pop("numbers")
    result["compared"] = compared           # last key of the line
    sys.stdout.flush()
    for name, v in numbers.items():
        if name not in compared:
            print(f"not held: {name} = {v!r}", file=sys.stderr)
    for name, (v, lim) in compared.items():
        print(f"compared: {name} = {v!r} (limit {lim!r}) "
              f"{'ok' if v <= lim else 'OVER'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
