"""Host time, or a count, from the program's own spans
(lightgbm_tpu/runtime/profiler.py: ``spans()``; each record has name, id,
parent, root, start_ns, end_ns, thread, counts).

params:
  "root":  a span name or a list of them; only finished ROOT spans of
           that name are taken (parent none), each with its descendants
  "which": "last" (the newest such root; the default) or "all"
  "match": [regex]  seconds of the descendants whose names match, as the
           union of their intervals (a matched span inside a matched span
           is not counted twice)
  "minus": [regex]  the roots' own seconds less that union
  "count": key      the sum of that count over the roots and their
           descendants, in place of time
  "scale": factor on the result (1000.0 gives ms)
With none of match, minus and count: the roots' whole duration, summed.

A program without the recorder (the parent of the PR that added it), a
run in which no such root finished, or a "match" that no span of those
roots answers to, reads nothing: ``None``.
"""

import re

from trace_reduce import union_ns


def records():
    """The recorder's snapshot, or None where the program has none."""
    try:
        from lightgbm_tpu.runtime import profiler
    except ImportError:
        return None
    snapshot = getattr(profiler, "spans", None)
    return snapshot() if snapshot is not None else None


def trees(recs, names, which="last"):
    """[(root, [descendants])] for the finished roots called ``names``."""
    if isinstance(names, str):
        names = [names]
    roots = [r for r in recs if r["parent"] is None and r["name"] in names]
    if which == "last":
        roots = roots[-1:]
    out = []
    for root in roots:
        kids = [r for r in recs
                if r["root"] == root["id"] and r["id"] != root["id"]]
        out.append((root, kids))
    return out


def matched(spans, patterns):
    rx = [re.compile(p) for p in patterns]
    return [r for r in spans if any(x.search(r["name"]) for x in rx)]


def read(ctx, params):
    recs = records()
    if not recs:
        return None
    found = trees(recs, params["root"], params.get("which", "last"))
    if not found:
        return None
    total, hits = 0.0, 0
    for root, kids in found:
        if "count" in params:
            total += sum(r["counts"].get(params["count"], 0)
                         for r in [root] + kids)
            continue
        whole = root["end_ns"] - root["start_ns"]
        if "match" in params or "minus" in params:
            part = matched(kids, params.get("match") or params["minus"])
            hits += len(part)
            covered = union_ns((r["start_ns"], r["end_ns"]) for r in part)
            whole = covered if "match" in params else whole - covered
        total += whole / 1e9
    if "match" in params and not hits:
        return None
    return total * params.get("scale", 1.0)
