"""What every kind's run does the same way: find the device, count
compilations, read the peak, trace one call of the window's own step."""

from __future__ import annotations

import shutil
import sys
import tempfile
from typing import Any, Callable, Dict, Optional

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Compiles:
    """Counts backend compilations from the moment it is made (the
    listener of the repo's chip_smoke.py)."""

    def __init__(self) -> None:
        import jax.monitoring
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_: Any) -> None:
        if event == COMPILE_EVENT:
            self.n += 1
            self.seconds += secs


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def find_device(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """The device as JAX reports it; no chip, or fewer chips than the
    cell asks for, is an error (``require_chip`` is off in tests only)."""
    from lightgbm_tpu.runtime.device import device_info, require_tpu
    device = require_tpu() if ctx["require_chip"] else device_info()
    chips = int(ctx["cell"]["chips"])
    if device["count"] < chips:
        raise RuntimeError(f"cell needs {chips} chips, JAX found "
                           f"{device['count']}")
    return device


def peak_bytes() -> int:
    """Peak bytes in use on the fullest chip."""
    import jax
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices()))


def traced(span: str, step: Callable[[], Any], ctx: Dict[str, Any]):
    """Run ``step`` once under the profiler inside the host span ``span``
    and return the reduction of that window (bench/trace_reduce.py)."""
    import jax
    import trace_reduce
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation(span):
            step()
        jax.profiler.stop_trace()
        raw = trace_reduce.load_xplane(trace_reduce.find_xplane(tmp))
        keep: Optional[Callable] = ctx.get("keep_trace")
        if keep:
            keep(raw)
        return trace_reduce.reduce(raw, window_span=span)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
