"""A trace loaded with each device operation's ``jax.named_scope`` path.

``trace_reduce.load_xplane`` keeps an event's name, which on the device
is the operation's HLO line and does not hold the scope it was traced
under. The profiler keeps that path beside it, as the statistic
``tf_op`` of the operation's metadata in the device plane
(``jit(scan_fn)/while/body/.../lgbm_rank_grad/sort``); jax's
``ProfileData`` does not show an event's metadata, the trace's own
protobuf classes do. ``load_xplane`` here appends that path to the names
of the operations that ran under an ``lgbm_`` scope, as
`` /*scope: <path>*/``, so that the accepted readers' ``match`` patterns
(readers/trace_time.py) can select one scope's operations
(``lgbm_rank_grad``, ``lgbm_rank_ndcg``, ``lgbm_valid_update``) while
every pattern that read the HLO line reads it as before. A program with
no such scope, or an installation without the protobuf classes, gives
names with no suffix: the match finds nothing and the metric is left
out.
"""

from __future__ import annotations

import re
import shutil
import tempfile
from typing import Any, Callable, Dict, Optional

import trace_reduce

SCOPE = re.compile(r"lgbm_[A-Za-z0-9_]+")
PATH_STAT = "tf_op"


def scopes_of(path: str) -> Dict[str, str]:
    """{operation name: scope path} for the device operations whose path
    holds an ``lgbm_`` scope."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception:
        return {}
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: Dict[str, str] = {}
    for plane in space.planes:
        if not re.search(trace_reduce.DEVICE_PLANE, plane.name):
            continue
        stat_name = {k: v.name for k, v in plane.stat_metadata.items()}
        for meta in plane.event_metadata.values():
            for stat in meta.stats:
                if stat_name.get(stat.metadata_id) == PATH_STAT \
                        and SCOPE.search(stat.str_value):
                    own = meta.display_name or trace_reduce.short_name(
                        meta.name).lstrip("%")
                    out[own] = stat.str_value
    return out


def load_xplane(path: str, keep_plane: str = r"^/(device|host):") -> Dict:
    raw = trace_reduce.load_xplane(path, keep_plane)
    scopes = scopes_of(path)
    if not scopes:
        return raw
    for plane in raw["planes"]:
        if not re.search(trace_reduce.DEVICE_PLANE, plane["name"]):
            continue
        for line in plane["lines"]:
            if not re.search(trace_reduce.OPS_LINE, line["name"]):
                continue
            for ev in line["events"]:
                own = trace_reduce.short_name(ev[0]).lstrip("%")
                scope = scopes.get(own)
                if scope:
                    ev[0] = f"{ev[0]} /*scope: {scope}*/"
    return raw


def traced(span: str, step: Callable[[], Any], ctx: Dict[str, Any]):
    """kinds/common.py ``traced`` with the scopes kept."""
    import jax
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation(span):
            step()
        jax.profiler.stop_trace()
        raw = load_xplane(trace_reduce.find_xplane(tmp))
        keep: Optional[Callable] = ctx.get("keep_trace")
        if keep:
            keep(raw)
        return trace_reduce.reduce(raw, window_span=span)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
