"""Share of the traced window's device idle time that lies under named
leaf spans of the program's last ``predict`` call, in per cent.

The device's idle gaps are on the profiler's clock
(``ctx["trace"].devices[0].gaps()``); the program's span records are on
``time.perf_counter_ns()``. The traced window is the ``bench:traced_pass``
annotation, which encloses exactly one ``Booster.predict`` call, so the
root span is laid centred into the window:

    t -> window[0] + (t - root.start) + (window_len - root_len) / 2

and the reader gives up (``None``) if the root is longer than the window
or shorter by more than 1 %: then the window held something else. A leaf
is a span of that call with no child span. params: {"root": the call's
root span name, "match": [regex] over leaf names}. No trace, no recorder
in the program, no such call, or a device that was never idle reads
nothing.
"""

from readers import program_span
from trace_reduce import union_ns


def read(ctx, params):
    tr = ctx.get("trace")
    recs = program_span.records()
    if tr is None or not tr.devices or not recs:
        return None
    found = program_span.trees(recs, params.get("root", "predict"), "last")
    if not found:
        return None
    root, kids = found[0]
    w0, w1 = tr.window
    root_len = root["end_ns"] - root["start_ns"]
    win_len = w1 - w0
    if win_len <= 0 or root_len > win_len \
            or win_len - root_len > 0.01 * win_len:
        return None
    shift = w0 - root["start_ns"] + (win_len - root_len) / 2.0
    parents = {r["parent"] for r in kids}
    leaves = program_span.matched(
        [r for r in kids if r["id"] not in parents], params["match"])
    spans = sorted((r["start_ns"] + shift, r["end_ns"] + shift)
                   for r in leaves)
    idle = covered = 0.0
    for g0, length in tr.devices[0].gaps():
        idle += length
        covered += union_ns(
            (max(s, g0), min(e, g0 + length)) for s, e in spans
            if e > g0 and s < g0 + length)
    if idle <= 0:
        return None
    return 100.0 * covered / idle
