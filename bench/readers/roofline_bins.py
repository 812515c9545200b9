"""Share of the chip's roofline with each column priced at its own bin
width: the least time the traced chunk's histogram work needs on this
chip (bench/work_cat.py, from the trees the chunk grew and the per-column
bin counts of ingest's mappers, the fact ``feature_bins``) over the
device time measured for it (as readers/trace_time.py reads it), in per
cent. params: the trace_time params that select the measured time: with
a ``match``, the matched kernels' share (``cat_wave_roofline``); with
none, the whole step's busy time (``cat_step_mfu``)."""

import work_cat
from readers import trace_time


def read(ctx, params):
    measured = trace_time.seconds(ctx, params)
    facts = ctx["facts"]
    trees = facts.get("last_chunk_trees")
    bins = facts.get("feature_bins")
    peak = ctx.get("peak")
    if not measured or not trees or not bins or not peak:
        return None
    least = work_cat.least_seconds(trees, facts["rows"], bins,
                                   int(ctx["config"]["gradient_bytes"]), peak)
    facts.setdefault("cat_roofline_bound_by", least["bound_by"])
    return 100.0 * least["seconds"] / measured
