#!/usr/bin/env python
"""Cross-check numeric performance claims in README/docs against the
bench result JSONs, so re-run benchmarks can't silently strand stale
numbers in the prose (docs/PERF.md links here; runs in the tier-1
suite via tests/test_stale_claims.py).

What counts as a claim:
  * multiplier tokens — ``70.3x`` / ``12.5×`` — on any line;
  * magnitude-suffixed rates — ``700M`` / ``2.3G`` — on lines that
    mention a per-second unit (``/s``).
Bound/approximate claims (token preceded by ``>=``/``<=``/``~``/
``≥``/``≤``) are deliberate statements, not measurements, and are
skipped.

A claim passes if it matches (within REL_TOL, to absorb display
rounding) any numeric leaf of any bench JSON, or any pairwise ratio of
leaves within one JSON file (speedup claims are usually a ratio of two
measured rates). Exit status 0 = all claims verified.
"""

import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC_PATHS = ["README.md", "docs/PERF.md", "docs/PARITY.md",
             "docs/SERVING.md", "docs/ROBUSTNESS.md", "docs/ONLINE.md"]
BENCH_GLOBS = ["BENCH_EXTRAS.json", "BENCH_ROWWISE.json",
               "BENCH_COMM.json", "BENCH_RESIL.json", "BENCH_SLO.json",
               "BENCH_ONLINE.json", "BENCH_FLEET.json", "BENCH_EXPORT.json",
               "BENCH_BATCHED.json", "BASELINE.json", "BENCH_BINNING.json"]
REL_TOL = 0.05          # claims are rounded for display (700M vs 680.4M)
SKIP_BEFORE = "≥≤<>~="  # bound / approximation markers: not measurements

MULT_RE = re.compile(r"(\d+(?:\.\d+)?)[x×](?![0-9A-Za-z])")
RATE_RE = re.compile(r"(\d+(?:\.\d+)?)([KMG])(?![0-9A-Za-z])")
SUFFIX = {"K": 1e3, "M": 1e6, "G": 1e9}


_RATE_KEY = re.compile(r"per_sec|qps|throughput|speedup|^value$",
                       re.IGNORECASE)

# duration-keyed leaves (p99_ms, phase_s, ...) are excluded from the
# match pool: doc claims are only ever multipliers or rates, so a
# latency reading can only *coincidentally* match one — and a bench
# that publishes per-tenant p50/p99 tables (BENCH_FLEET/BENCH_EXPORT)
# would otherwise blanket the 1-200 range and blunt the check.
# `_per_s` keys are rates, not durations, hence the lookbehind.
_DURATION_KEY = re.compile(r"(_ms|_us|_ns|(?<!_per)_s)$")


def _numeric_leaves(obj, out, groups, key=None):
    """Collect float leaves into `out`; each dict's rate-like values
    (per_sec / qps / throughput keys) form one group in `groups` —
    speedup claims compare two rates measured in the same record.
    Keeping the ratio pool to rate siblings is what gives the check
    teeth: ratios over arbitrary leaf pairs (row counts vs rates)
    cover enough of the number line to verify anything."""
    if isinstance(obj, bool):
        return
    if isinstance(obj, (int, float)):
        if key is None or not _DURATION_KEY.search(str(key)):
            out.append(float(obj))
    elif isinstance(obj, dict):
        own = [float(v) for k, v in obj.items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)
               and _RATE_KEY.search(str(k))]
        if len(own) > 1:
            groups.append(own)
        for k, v in obj.items():
            _numeric_leaves(v, out, groups, k)
    elif isinstance(obj, list):
        for v in obj:
            _numeric_leaves(v, out, groups, key)


def load_bench_values():
    """All numeric leaves, plus sibling-pair ratios (> 1)."""
    values, ratios = [], []
    for pat in BENCH_GLOBS:
        for path in sorted(glob.glob(os.path.join(ROOT, pat))):
            try:
                with open(path) as f:
                    data = json.load(f)
            except Exception:
                continue
            groups = []
            _numeric_leaves(data, values, groups)
            for grp in groups:
                pos = [v for v in grp if v > 0]
                for a in pos:
                    for b in pos:
                        if a > b:
                            ratios.append(a / b)
    return values, ratios


_BOUND_WORDS = re.compile(r"(?:worst[- ]case|up to|at most|bounded by)"
                          r"\s*$", re.IGNORECASE)


def _skipped(text, start):
    """Bound/approx markers directly before the token: comparison
    glyphs (spaces allowed) or bound phrasing like 'worst case 2x' —
    analytic statements, not measurements."""
    i = start - 1
    while i >= 0 and text[i] == " ":
        i -= 1
    if i >= 0 and text[i] in SKIP_BEFORE:
        return True
    return bool(_BOUND_WORDS.search(text[:start]))


def claims_in_file(path):
    with open(os.path.join(ROOT, path), encoding="utf-8") as f:
        lines = f.read().splitlines()
    for ln, line in enumerate(lines, 1):
        for m in MULT_RE.finditer(line):
            # reject things like "4M x 28" (dimension, not a multiplier)
            if _skipped(line, m.start()) or \
                    (m.start() and line[m.start() - 1].isalnum()):
                continue
            yield path, ln, m.group(0), float(m.group(1))
        if "/s" in line:
            for m in RATE_RE.finditer(line):
                if _skipped(line, m.start()):
                    continue
                yield (path, ln, m.group(0),
                       float(m.group(1)) * SUFFIX[m.group(2)])


def verify(value, bench_values, bench_ratios):
    for pool in (bench_values, bench_ratios):
        for v in pool:
            if v and abs(value - v) <= REL_TOL * max(abs(v), abs(value)):
                return True
    return False


def main():
    bench_values, bench_ratios = load_bench_values()
    if not bench_values:
        print("check_stale_claims: no bench JSONs found — nothing to "
              "verify against")
        return 0
    stale, checked = [], 0
    for path in DOC_PATHS:
        if not os.path.exists(os.path.join(ROOT, path)):
            continue
        for path, ln, token, value in claims_in_file(path):
            checked += 1
            if not verify(value, bench_values, bench_ratios):
                stale.append((path, ln, token, value))
    if stale:
        print("Stale performance claims (no bench JSON value or ratio "
              f"within {REL_TOL:.0%}):")
        for path, ln, token, value in stale:
            print(f"  {path}:{ln}: '{token}' ({value:g})")
        print("Re-run the benches (bench.py / bench_extras.py) or fix "
              "the prose.")
        return 1
    print(f"check_stale_claims: {checked} claims verified against "
          f"{len(bench_values)} bench values")
    return 0


if __name__ == "__main__":
    sys.exit(main())
