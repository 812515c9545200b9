"""Share of the chip's roofline for a scoring pass: the least time the
walk needs on this chip (every row read once and its margin written,
one comparison per level of each tree: bench/work.py) over the device
time measured for the traced pass, in per cent. params: the trace_time
params that select the measured time."""

import work
from readers import trace_time


def read(ctx, params):
    measured = trace_time.seconds(ctx, params)
    facts, peak = ctx["facts"], ctx.get("peak")
    if not measured or not peak or not facts.get("forest_mean_depth"):
        return None
    least = work.score_least_seconds(
        facts["rows"] * facts.get("traced_passes", 1), facts["features"],
        facts["forest_mean_depth"], peak)
    return 100.0 * least["seconds"] / measured
