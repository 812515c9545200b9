"""Validate BENCHMARK.json and everything under bench/ on a CPU, with no
chip and without importing the program: names, units, files, cross
references, and the trace reduction on the recorded fixture.

    python3 bench/check.py          # exit 0 and "check: ok", or the faults

Run it before the first chip call and again before finishing: a manifest
the driver refuses costs the whole PR.
"""

from __future__ import annotations

import json
import os
import re
import sys
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
MAX_CELLS = 24


def load(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def line_ok(s: Any) -> bool:
    return isinstance(s, str) and 1 <= len(s) <= 200 \
        and "\n" not in s and "\t" not in s


def check_manifest(m: Dict[str, Any], faults: List[str]) -> None:
    def bad(msg: str) -> None:
        faults.append(msg)

    if set(m) != TOP_KEYS:
        bad(f"top-level keys {sorted(m)} != {sorted(TOP_KEYS)}")
        return
    if os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) > 64 * 1024:
        bad("BENCHMARK.json over 64 KiB")
    cmd = m["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32
            and all(line_ok(w) for w in cmd)):
        bad("command: 1 to 32 words of 1 to 200 characters")
    for w in cmd:
        if w.startswith("/") or ".." in w.split("/"):
            bad(f"command word {w!r} leaves the repo")
    paths = m["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        bad("paths: 1 to 16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            bad(f"path {p!r}")
    rs = m["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        bad("run_seconds: a whole number from 1 to 51")
    else:
        need = (2 + 14 * MAX_CELLS) * (rs + 60) + MAX_CELLS * 180 + 1200
        if need > 43200:
            bad(f"run_seconds {rs}: a full check of {MAX_CELLS} cells "
                f"needs {need} s > 43200")

    under = lambda f: any(f == p or f.startswith(p.rstrip("/") + "/")
                          for p in paths)
    cfg_names, files = set(), set()
    if not 1 <= len(m["configs"]) <= 24:
        bad("configs: 1 to 24")
    for c in m["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        if not NAME.match(c["name"]) or c["name"] in cfg_names:
            bad(f"config name {c['name']!r}")
        cfg_names.add(c["name"])
        if not line_ok(c["source"]) or not line_ok(c["why"]):
            bad(f"config {c['name']}: source/why 1 to 200 chars, one line")
        if not PATH.match(c["file"]) or not under(c["file"]) \
                or c["file"] in files:
            bad(f"config {c['name']}: file {c['file']!r}")
        files.add(c["file"])
        if not os.path.isfile(os.path.join(ROOT, c["file"])):
            bad(f"config {c['name']}: {c['file']} does not exist")
        if len(c["reduced"]) > 16 or not all(NAME.match(k)
                                             for k in c["reduced"]):
            bad(f"config {c['name']}: reduced")

    cells: Dict[str, Dict] = {}
    pairs = set()
    if not 1 <= len(m["workloads"]) <= MAX_CELLS:
        bad("workloads: 1 to 24")
    for w in m["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        for k in ("name", "config", "traffic"):
            if not NAME.match(w[k]):
                bad(f"workload {w['name']}: {k} {w[k]!r}")
        if w["name"] in cells:
            bad(f"workload {w['name']} twice")
        cells[w["name"]] = w
        if w["config"] not in cfg_names:
            bad(f"workload {w['name']}: unknown config {w['config']}")
        if (w["config"], w["traffic"]) in pairs:
            bad(f"workload {w['name']}: config and traffic pair twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            bad(f"workload {w['name']}: chips {w['chips']}")
        if not line_ok(w["why"]):
            bad(f"workload {w['name']}: why 1 to 200 chars on one line "
                f"(has {len(w['why'])})")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        bad(f"{four} four-chip cells of {len(cells)}")
    used = {w["config"] for w in cells.values()}
    for c in cfg_names - used:
        bad(f"config {c} used by no cell")

    names = set()
    e2e: Dict[str, Dict] = {}
    if not 1 <= len(m["end_to_end"]) <= 16:
        bad("end_to_end: 1 to 16")
    for e in m["end_to_end"]:
        keys = set(e) - {"workloads"}
        if keys != {"name", "unit", "better", "bound", "source"}:
            bad(f"end_to_end {e.get('name')}: keys {sorted(e)}")
            continue
        check_metric_common(e, names, cells, faults)
        if e["source"] not in ("host_clock", "device_trace"):
            bad(f"end_to_end {e['name']}: source {e['source']}")
        if not (isinstance(e["bound"], (int, float))
                and 0.01 <= e["bound"] <= 0.1):
            bad(f"end_to_end {e['name']}: bound {e['bound']}")
        e2e[e["name"]] = e
    if "setup_s" not in e2e:
        bad("end_to_end lacks setup_s")
    layers = set()
    if not 1 <= len(m["per_layer"]) <= 128:
        bad("per_layer: 1 to 128")
    for p in m["per_layer"]:
        keys = set(p) - {"workloads"}
        if keys != {"name", "unit", "better", "source", "layer", "moves"}:
            bad(f"per_layer {p.get('name')}: keys {sorted(p)}")
            continue
        check_metric_common(p, names, cells, faults)
        if p["source"] not in SOURCES:
            bad(f"per_layer {p['name']}: source {p['source']}")
        if not NAME.match(p["layer"]):
            bad(f"per_layer {p['name']}: layer {p['layer']!r} is not one "
                "token of letters, digits, _ . -")
        layers.add(p["layer"])
        if p["moves"] not in e2e:
            bad(f"per_layer {p['name']}: moves unknown {p['moves']!r}")
        if re.search(r"roofline|mfu", p["name"]) and p["unit"] != "%":
            bad(f"per_layer {p['name']}: a roofline or mfu share is in %")


def check_metric_common(e: Dict, names: set, cells: Dict,
                        faults: List[str]) -> None:
    if not NAME.match(e["name"]) or e["name"] in names:
        faults.append(f"metric name {e['name']!r} bad or twice")
    names.add(e["name"])
    if not UNIT.match(e["unit"]):
        faults.append(f"metric {e['name']}: unit {e['unit']!r}")
    if e["better"] not in ("lower", "higher"):
        faults.append(f"metric {e['name']}: better {e['better']!r}")
    for w in e.get("workloads", []):
        if w not in cells:
            faults.append(f"metric {e['name']}: unknown cell {w}")


def check_files(m: Dict[str, Any], faults: List[str]) -> None:
    """Every cell's own files, and that they agree with the manifest."""
    bad = faults.append
    e2e = {e["name"]: e for e in m["end_to_end"]}
    per = {p["name"]: p for p in m["per_layer"]}
    for root, _, fnames in os.walk(BENCH_DIR):
        if "__pycache__" in root:
            continue
        for fn in fnames:
            rel = os.path.relpath(os.path.join(root, fn), ROOT)
            if not PATH.match(rel):
                bad(f"file name {rel!r} outside the alphabet")
    listed: Dict[str, set] = {n: set() for n in per}
    for w in m["workloads"]:
        path = os.path.join(BENCH_DIR, "workloads", w["name"] + ".json")
        if not os.path.isfile(path):
            bad(f"cell {w['name']}: no {os.path.relpath(path, ROOT)}")
            continue
        cell = load(path)
        for k in ("config", "traffic", "chips", "why"):
            if cell.get(k) != w[k]:
                bad(f"cell {w['name']}: {k} differs from BENCHMARK.json")
        kind = os.path.join(BENCH_DIR, "kinds", cell["kind"] + ".py")
        if not os.path.isfile(kind):
            bad(f"cell {w['name']}: no kind {cell['kind']}")
        if "setup_s" not in cell["end_to_end"] \
                or len(cell["end_to_end"]) < 2 or not cell["per_layer"]:
            bad(f"cell {w['name']}: needs setup_s, another end-to-end "
                "metric and a per-layer metric")
        for name in cell["end_to_end"]:
            if name not in e2e:
                bad(f"cell {w['name']}: end-to-end {name} not in manifest")
            elif "workloads" in e2e[name] \
                    and w["name"] not in e2e[name]["workloads"]:
                bad(f"cell {w['name']}: not listed under {name}")
        for name in cell["per_layer"]:
            if name not in per:
                bad(f"cell {w['name']}: per-layer {name} not in manifest")
                continue
            listed[name].add(w["name"])
            if per[name]["moves"] not in cell["end_to_end"]:
                bad(f"cell {w['name']}: {name} moves "
                    f"{per[name]['moves']}, which the cell does not report")
        if not cell.get("limits"):
            bad(f"cell {w['name']}: no limits, so never correct")
    for name, p in per.items():
        path = os.path.join(BENCH_DIR, "metrics", name + ".json")
        if not os.path.isfile(path):
            bad(f"metric {name}: no {os.path.relpath(path, ROOT)}")
            continue
        spec = load(path)
        for k in ("unit", "better", "source", "layer", "moves"):
            if spec.get(k) != p[k]:
                bad(f"metric {name}: {k} differs from BENCHMARK.json")
        reader = os.path.join(BENCH_DIR, "readers", spec["reader"] + ".py")
        if not os.path.isfile(reader):
            bad(f"metric {name}: no reader {spec['reader']}")
        if "workloads" in p and set(p["workloads"]) != listed[name]:
            bad(f"metric {name}: workloads {sorted(p['workloads'])} != "
                f"cells that name it {sorted(listed[name])}")
    peaks = load(os.path.join(BENCH_DIR, "peaks.json"))
    for kind, pk in peaks.items():
        for k in ("source", "bf16_flops_per_s", "hbm_bytes_per_s"):
            if k not in pk:
                bad(f"peaks {kind}: lacks {k}")


def check_fixture(faults: List[str]) -> None:
    """The trace reduction gives the recorded numbers on the fixture."""
    import trace_reduce
    fx = os.path.join(BENCH_DIR, "fixtures")
    raw = load(os.path.join(fx, "trace_small.json"))
    want = load(os.path.join(fx, "trace_small.expected.json"))
    red = trace_reduce.reduce(raw, window_span=want.get("window_span"))
    got = {"window_s": red.window_s, "busy_s": red.busy_s,
           "n_devices": len(red.devices),
           "n_leaves": sum(len(d.leaves) for d in red.devices)}
    for label, pats in want.get("matched", {}).items():
        got["matched_" + label] = red.matched_s(pats)
    for k, v in want["values"].items():
        g = got.get(k)
        if g is None or abs(g - v) > 1e-9 * max(1.0, abs(v)):
            faults.append(f"fixture: {k} = {g!r}, recorded {v!r}")
    # the hand-made case: nesting, overlap and a gap
    ev = [["frame", 0, 100], ["a", 10, 20], ["b", 25, 10], ["c", 50, 30],
          ["c.inner", 55, 5]]
    leaves = trace_reduce.leaf_events(ev)
    if [n for n, _, _ in leaves] != ["a", "b", "c.inner"]:
        faults.append(f"leaf_events: {leaves}")
    if trace_reduce.union_ns([(0, 10), (5, 20), (30, 40)]) != 30:
        faults.append("union_ns")


def main() -> int:
    faults: List[str] = []
    m = load(os.path.join(ROOT, "BENCHMARK.json"))
    check_manifest(m, faults)
    if not faults:
        check_files(m, faults)
    check_fixture(faults)
    for f in faults:
        print("check: " + f)
    print("check: ok" if not faults else f"check: {len(faults)} fault(s)")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
