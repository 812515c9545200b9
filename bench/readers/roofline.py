"""Share of the chip's roofline: the least time the traced chunk's
histogram work needs on this chip (bench/work.py, from the trees the
chunk grew and the configuration's widths) over the device time measured
for it (as readers/trace_time.py reads it), in per cent. params: the
trace_time params that select the measured time."""

import work
from readers import trace_time


def read(ctx, params):
    measured = trace_time.seconds(ctx, params)
    trees = ctx["facts"].get("last_chunk_trees")
    peak = ctx.get("peak")
    if not measured or not trees or not peak:
        return None
    cfg = ctx["config"]
    least = work.least_seconds(
        trees, ctx["facts"]["rows"], ctx["facts"]["features"],
        int(cfg["params"]["max_bin"]), int(cfg["gradient_bytes"]), peak)
    ctx["facts"].setdefault("roofline_bound_by", least["bound_by"])
    return 100.0 * least["seconds"] / measured
