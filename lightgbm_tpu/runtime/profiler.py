"""Host spans on the profiler's clock, and device-fenced stage profiling.

Two layers live here:

 * ``span`` / ``count`` — the program's one span-and-counter primitive.
   A span times what the HOST did between two points and never fences
   the device: no ``block_until_ready``, no barrier. Each span also
   enters ``jax.profiler.TraceAnnotation("lgbm:" + name)``, so under
   ``jax.profiler.start_trace`` it lands in the trace's ``/host:CPU``
   plane beside the device planes, over the idle gap it explains; with
   no profiler session the annotation is inert. The annotation carries
   the span's ring id as its statistic ``ring_id``: the trace's times
   are relative to its session and the ring's are ``perf_counter_ns``,
   so one joined pair gives the offset for every span of the process.
   Finished spans go to one bounded ring (oldest dropped, and counted:
   ``spans_dropped()``); ``spans()`` snapshots it (all spans of one
   call share their ``root``). Backend
   compilations are counted where they happen: one ``jax.monitoring``
   listener adds ``compiles`` and ``compile_s`` to the innermost open
   span of the compiling thread, and a second says which of them the
   persistent compile cache answered (``cache_hits``) and which it had
   to compile and store (``cache_misses``): a trainer's program that
   misses on a second dataset of a known shape holds data as a constant
   (docs/PERF.md §7).
   The recorder is on by default at call / chunk / ingest-phase
   granularity; ``set_spans(False)`` turns recording and annotation off.
 * ``StageProfiler`` — the operator's fenced per-iteration profile
   (``device_profile=true``). JAX dispatches asynchronously, so every
   span is fenced with a device barrier (``jax.effects_barrier`` +
   blocking the live arrays) before and after; the host clock then
   brackets real device wall time. Outside an iteration (``bin``,
   ``autotune``) it opens the primitive's span between its fences, so
   there is one recorder and one name space; the spans of an iteration
   stay in its own per-iteration ring, since the span ring is never
   written per tree. Each iteration records named spans plus an
   ``other`` catch-all (iteration wall minus the sum of explicit spans)
   so the per-stage breakdown always sums to the measured wall time. A
   bounded ring buffer keeps the most recent iterations; totals,
   throughput counters (row-iters/s) and an HBM watermark
   (``jax.local_devices()[0].memory_stats()``) accumulate for the whole
   run. ``to_dict``/``export_json`` emit the JSON shape consumed by
   bench.py / BENCH_*.json and by the ``--profile`` CLI flag.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import threading
import time
from typing import Any, Callable, Dict, List, Optional

SPAN_PREFIX = "lgbm:"           # a span's name in a profiler trace
# a pass of the device predictor leaves six spans a row block (118 records
# at 1M x 968 rows), and a process's set-up spans must outlast some tens
# of passes: the benchmark reads them after its window
SPAN_RING_SIZE = 16384
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the persistent compile cache's own events -> the count each adds
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                "/jax/compilation_cache/cache_misses": "cache_misses"}


def device_barrier() -> None:
    """Wait for all dispatched device work (best effort; never raises).

    ``effects_barrier`` flushes ordered effects, then blocking every live
    array flushes the async dispatch queue — together a full fence on
    every backend we run on (CPU/TPU, single- or multi-device)."""
    try:
        import jax
        jax.effects_barrier()
        for d in jax.live_arrays():
            d.block_until_ready()
    except Exception:
        pass


# ---------------------------------------------------------------------------
# spans and counts
# ---------------------------------------------------------------------------

def _trace_annotation(name: str, ring_id: int):
    """The profiler's own host annotation for ``name``, carrying the
    span's ring id (jax imported lazily: this module stays importable
    host-only)."""
    import jax.profiler
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, ring_id=ring_id)


class _Span:
    """One open (then finished) span; ``to_dict`` is its record."""

    __slots__ = ("_rec", "name", "id", "parent", "root", "start_ns",
                 "end_ns", "thread", "counts", "_ann")

    def __init__(self, rec: "SpanRecorder", name: str,
                 counts: Dict[str, float]) -> None:
        self._rec = rec
        self.name = name
        self.counts = counts

    def __enter__(self) -> "_Span":
        stack = self._rec._stack()
        self.id = next(self._rec._ids)
        if stack:
            self.parent, self.root = stack[-1].id, stack[-1].root
        else:
            self.parent, self.root = None, self.id
        self.thread = threading.current_thread().name
        stack.append(self)
        self._ann = _trace_annotation(self.name, self.id)
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.end_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._rec._stack().pop()
        ring = self._rec.ring
        if len(ring) == ring.maxlen:
            self._rec.dropped += 1
        ring.append(self)

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "id": self.id, "parent": self.parent,
                "root": self.root, "start_ns": self.start_ns,
                "end_ns": self.end_ns, "thread": self.thread,
                "counts": dict(self.counts)}


_NO_SPAN = contextlib.nullcontext()


class SpanRecorder:
    """Finished spans in one bounded ring, open spans on a stack per
    thread (a span's parent is the innermost open span of its own
    thread; its root is the outermost, whose id every span of one
    ``predict`` call, one chunk, one ``construct`` shares)."""

    def __init__(self, maxlen: int = SPAN_RING_SIZE) -> None:
        self.ring: collections.deque = collections.deque(maxlen=maxlen)
        self.enabled = True
        self.compiles_outside_spans = 0
        self.dropped = 0                # spans pushed out of a full ring
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> List[_Span]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def span(self, name: str, **counts: float):
        """Context manager: time the block on the host clock (no device
        fence) and record it; ``counts`` start the span's counts."""
        if not self.enabled:
            return _NO_SPAN
        return _Span(self, name, counts)

    def count(self, **counts: float) -> None:
        """Add to the counts of the innermost open span of this thread
        (nothing open: nothing counted)."""
        stack = self._stack()
        if stack:
            c = stack[-1].counts
            for k, v in counts.items():
                c[k] = c.get(k, 0) + v

    def on_compile(self, secs: float) -> None:
        stack = self._stack()
        if stack:
            self.count(compiles=1, compile_s=secs)
        elif self.enabled:
            self.compiles_outside_spans += 1

    def spans(self) -> List[Dict[str, Any]]:
        """Snapshot of the ring, oldest first, as plain dicts."""
        return [s.to_dict() for s in list(self.ring)]


_RECORDER = SpanRecorder()
_compile_listener_on = False


def _on_duration_event(event: str, secs: float, **_: Any) -> None:
    if event == COMPILE_EVENT:
        _RECORDER.on_compile(secs)


def _on_event(event: str, **_: Any) -> None:
    name = CACHE_EVENTS.get(event)
    if name is not None:
        _RECORDER.count(**{name: 1})


def span(name: str, **counts: float):
    """``with span("predict/upload", bytes_up=n): ...`` on the process's
    recorder. Names are the contract the benchmark's readers match
    (PERF.md section 3 lists each with the metric it feeds)."""
    global _compile_listener_on
    if not _compile_listener_on and _RECORDER.enabled:
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(
            _on_duration_event)
        jax.monitoring.register_event_listener(_on_event)
        _compile_listener_on = True
    return _RECORDER.span(name, **counts)


def count(**counts: float) -> None:
    _RECORDER.count(**counts)


def spans() -> List[Dict[str, Any]]:
    return _RECORDER.spans()


def compiles_outside_spans() -> int:
    return _RECORDER.compiles_outside_spans


def spans_dropped() -> int:
    """Spans pushed out of the full ring so far: a reader that finds
    children missing can tell an overwritten record from a short one."""
    return _RECORDER.dropped


def set_spans(on: bool) -> None:
    """Turn recording and annotation on (the default) or off. Spans
    already open finish and are kept."""
    _RECORDER.enabled = bool(on)


def _hbm_peak_bytes() -> Optional[int]:
    """Current peak device memory, or None where the backend has no
    allocator stats (CPU, some TPU runtimes)."""
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats()
        if not stats:
            return None
        return int(stats.get("peak_bytes_in_use",
                             stats.get("bytes_in_use", 0))) or None
    except Exception:
        return None


def _median(vals: List[float]) -> float:
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


class StageProfiler:
    """Per-iteration stage spans, device-fenced, with a ring buffer.

    Usage from the training loop::

        prof.iter_start()
        with prof.span("boost"): ...
        with prof.span("grow"): ...
        prof.iter_end(n_rows=...)

    Spans outside an iteration (e.g. the one-time "bin" upload at init)
    accumulate into totals only. ``clock`` is injectable for tests.
    """

    RING_SIZE = 512

    def __init__(self, ring_size: int = RING_SIZE,
                 clock: Callable[[], float] = time.perf_counter,
                 barrier: Callable[[], None] = device_barrier,
                 record_spans: bool = True) -> None:
        self._clock = clock
        self._barrier = barrier
        # serving's per-batch accounting passes False: the span ring is
        # for calls, chunks and ingest phases, never per request
        self._record_spans = record_spans
        self.ring: collections.deque = collections.deque(maxlen=ring_size)
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self.counters: Dict[str, float] = {}
        self.extras: Dict[str, Any] = {}
        self.n_iters = 0
        self.total_wall = 0.0
        self.total_rows = 0
        self.hbm_peak_bytes: Optional[int] = None
        self._iter_t0: Optional[float] = None
        self._iter_spans: Optional[Dict[str, float]] = None
        self._iter_fields: Optional[Dict[str, Any]] = None
        # cross-rank straggler detection (docs/ROBUSTNESS.md): per-stage
        # lists of per-iteration [rank0_s, rank1_s, ...] span rows, fed
        # by the multi-host training loop (or synthetically by tests)
        self.rank_spans: Dict[str, List[List[float]]] = {}
        self.straggler_threshold = 1.5
        # multi-tenant serving (serving/fleet.py): spans tagged with a
        # tenant ALSO accumulate into a per-tenant table, exported as
        # "stages_by_tenant" — per-model device time never aggregates
        # across a shared pool
        self.tenant_totals: Dict[str, Dict[str, float]] = {}

    # -- span recording ---------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str, tenant: Optional[str] = None):
        """Fence the device, time the block, fence again. Inside an
        iteration the span lands in that iteration's record; outside it
        accumulates into totals only (init-scope work such as "bin") and
        the block also runs under the recorder's ``span(name)``, between
        the fences. With ``tenant`` set, the span also lands in that
        tenant's row of the per-tenant table (fleet serving)."""
        self._barrier()
        t0 = self._clock()
        recorded = self._record_spans and self._iter_spans is None
        try:
            with span(name) if recorded else _NO_SPAN:
                yield
        finally:
            self._barrier()
            dt = self._clock() - t0
            self.totals[name] = self.totals.get(name, 0.0) + dt
            self.counts[name] = self.counts.get(name, 0) + 1
            if self._iter_spans is not None:
                self._iter_spans[name] = self._iter_spans.get(name, 0.0) + dt
            if tenant is not None:
                row = self.tenant_totals.setdefault(str(tenant), {})
                row[name] = row.get(name, 0.0) + dt

    def iter_start(self) -> None:
        self._barrier()
        self._iter_spans = {}
        self._iter_fields = {}
        self._iter_t0 = self._clock()

    def iter_meta(self, **fields: Any) -> None:
        """Attach host-known metadata (e.g. ``comm_mode``/``comm_bytes``
        for the distributed histogram exchange) to the CURRENT
        iteration's ring record. The growers are single fused jits, so
        collective traffic can't be span-timed from the host; these
        analytic fields are the per-iteration record of what went over
        the wire. No-op outside an iteration."""
        if self._iter_fields is not None:
            self._iter_fields.update(fields)

    def iter_end(self, n_rows: int = 0) -> None:
        if self._iter_t0 is None:
            return
        self._barrier()
        wall = self._clock() - self._iter_t0
        spans = self._iter_spans or {}
        # catch-all: host-side work between spans, so the stage breakdown
        # always sums to the iteration wall time
        other = wall - sum(spans.values())
        if other > 0.0:
            spans["other"] = other
            self.totals["other"] = self.totals.get("other", 0.0) + other
        rec: Dict[str, Any] = {"iter": self.n_iters, "wall_s": wall,
                               "stages_s": spans}
        if self._iter_fields:
            rec.update(self._iter_fields)
        self.ring.append(rec)
        self.n_iters += 1
        self.total_wall += wall
        self.total_rows += int(n_rows)
        self._iter_t0 = None
        self._iter_spans = None
        self._iter_fields = None
        peak = _hbm_peak_bytes()
        if peak is not None:
            self.hbm_peak_bytes = max(self.hbm_peak_bytes or 0, peak)

    def add_counter(self, name: str, value: float) -> None:
        self.counters[name] = self.counters.get(name, 0.0) + value

    def record_batched_chunk(self, n_iters: int, wall_s: float,
                             n_rows: int = 0, **fields: Any) -> None:
        """Synthesize per-iteration ring records for a host-free scan
        chunk (models/gbdt.py:train_iters_batched, docs/PERF.md §7). One
        scan launch covers ``n_iters`` boosting iterations with no host
        boundary to span-time, so the chunk wall time is attributed
        evenly across its iterations under a single "scan" stage and
        each record carries ``batched: True`` — `device_profile=true`
        output keeps the same {iter, wall_s, stages_s} schema either
        path takes."""
        if n_iters <= 0:
            return
        per = wall_s / n_iters
        rows_per = int(n_rows) // n_iters
        for _ in range(n_iters):
            rec: Dict[str, Any] = {"iter": self.n_iters, "wall_s": per,
                                   "stages_s": {"scan": per},
                                   "batched": True}
            if fields:
                rec.update(fields)
            self.ring.append(rec)
            self.n_iters += 1
            self.total_wall += per
            self.total_rows += rows_per
        self.totals["scan"] = self.totals.get("scan", 0.0) + wall_s
        self.counts["scan"] = self.counts.get("scan", 0) + n_iters
        peak = _hbm_peak_bytes()
        if peak is not None:
            self.hbm_peak_bytes = max(self.hbm_peak_bytes or 0, peak)

    HBM_SAMPLE_CAP = 4096

    def sample_hbm(self, tag: str = "") -> Optional[int]:
        """Record one HBM-watermark sample (train+serve coexistence
        profiling, docs/ONLINE.md): appended to ``extras["hbm_watermark"]``
        and folded into the run peak. ``peak_bytes`` is None where the
        backend has no allocator stats (CPU) — the sample is still
        recorded so the export shape is backend-independent."""
        peak = _hbm_peak_bytes()
        if peak is not None:
            self.hbm_peak_bytes = max(self.hbm_peak_bytes or 0, peak)
        samples = self.extras.setdefault("hbm_watermark", [])
        if len(samples) < self.HBM_SAMPLE_CAP:
            samples.append({"seq": len(samples), "tag": str(tag),
                            "peak_bytes": peak})
        return peak

    # -- straggler detection ----------------------------------------------

    def record_rank_spans(self, stage: str, spans,
                          threshold: Optional[float] = None) -> None:
        """One iteration's per-rank wall seconds for ``stage``."""
        if threshold is not None:
            self.straggler_threshold = float(threshold)
        row = [float(s) for s in spans]
        if row:
            self.rank_spans.setdefault(stage, []).append(row)

    def straggler_report(self) -> Dict[str, Any]:
        """Cross-rank span skew per stage: each rank's mean span over
        the recorded iterations, the cross-rank median, and the ranks
        whose mean exceeds ``straggler_threshold`` x median — a
        persistently slow rank, not one noisy iteration."""
        out: Dict[str, Any] = {}
        for stage, rows in self.rank_spans.items():
            n_ranks = min(len(r) for r in rows)
            if n_ranks == 0:
                continue
            mean = [sum(r[i] for r in rows) / len(rows)
                    for i in range(n_ranks)]
            med = _median(mean)
            out[stage] = {
                "n_iters": len(rows),
                "mean_s_by_rank": [round(v, 6) for v in mean],
                "median_s": round(med, 6),
                "skew": round(max(mean) / med, 4) if med > 0 else 0.0,
                "threshold": self.straggler_threshold,
                "straggler_ranks": [
                    i for i, v in enumerate(mean)
                    if med > 0 and v > self.straggler_threshold * med],
            }
        return out

    # -- export -----------------------------------------------------------

    def row_iters_per_sec(self) -> Optional[float]:
        if self.total_wall <= 0.0 or self.total_rows <= 0:
            return None
        return self.total_rows / self.total_wall

    def to_dict(self) -> Dict[str, Any]:
        stages = {n: round(v, 6) for n, v in
                  sorted(self.totals.items(), key=lambda kv: -kv[1])}
        out: Dict[str, Any] = {
            "n_iters": self.n_iters,
            "total_wall_s": round(self.total_wall, 6),
            "stages_s": stages,
            "stage_counts": dict(self.counts),
            "ring": list(self.ring),
        }
        rps = self.row_iters_per_sec()
        if rps is not None:
            out["row_iters_per_sec"] = round(rps, 1)
        if self.counters:
            out["counters"] = {n: round(v, 6)
                               for n, v in self.counters.items()}
        if self.hbm_peak_bytes is not None:
            out["hbm_peak_bytes"] = self.hbm_peak_bytes
        if self.rank_spans:
            out["stragglers"] = self.straggler_report()
        if self.tenant_totals:
            out["stages_by_tenant"] = {
                t: {n: round(v, 6) for n, v in
                    sorted(row.items(), key=lambda kv: -kv[1])}
                for t, row in sorted(self.tenant_totals.items())}
        if self.extras:
            out.update(self.extras)
        return out

    def export_json(self, path: str = "") -> str:
        """Serialize; when ``path`` is set also write the file."""
        text = json.dumps(self.to_dict(), indent=2, sort_keys=False)
        if path:
            with open(path, "w") as f:
                f.write(text + "\n")
        return text


class LatencyStats:
    """Bounded latency reservoir with exact percentiles over the kept
    tail (most recent ``maxlen`` samples). Shared by the serving metrics
    (p50/p99 request latency) and any future per-event consumer; totals
    (count/sum) cover the whole run, percentiles the tail window."""

    def __init__(self, maxlen: int = 8192) -> None:
        self.buf: collections.deque = collections.deque(maxlen=maxlen)
        self.count = 0
        self.total = 0.0
        self.max_s = 0.0

    def record(self, seconds: float) -> None:
        self.buf.append(seconds)
        self.count += 1
        self.total += seconds
        if seconds > self.max_s:
            self.max_s = seconds

    def percentile(self, q: float) -> Optional[float]:
        """q in [0, 100] over the tail window; None when empty."""
        if not self.buf:
            return None
        s = sorted(self.buf)
        idx = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
        return s[idx]

    def to_dict(self) -> Dict[str, Any]:
        if self.count == 0:
            return {"count": 0}
        return {
            "count": self.count,
            "mean_ms": round(self.total / self.count * 1e3, 3),
            "p50_ms": round((self.percentile(50.0) or 0.0) * 1e3, 3),
            "p99_ms": round((self.percentile(99.0) or 0.0) * 1e3, 3),
            "max_ms": round(self.max_s * 1e3, 3),
        }


def pallas_kernel_names(fn: Callable, *args: Any,
                        **kwargs: Any) -> List[str]:
    """The kernel name of every Pallas launch site in ``fn``'s jaxpr.

    Traces ``fn`` on the given args (abstract — nothing executes) and
    walks every equation, recursing into sub-jaxprs (cond branches,
    while bodies, pjit/scan calls), collecting each ``pallas_call``'s
    name: what the site passed as ``name=`` (utils.kernel_name), else
    the kernel function's own name."""
    import jax

    def sub_jaxprs(params: Dict[str, Any]):
        for v in params.values():
            for x in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(x, "eqns"):              # raw Jaxpr
                    yield x
                elif hasattr(x, "jaxpr"):           # ClosedJaxpr
                    yield x.jaxpr

    def walk(jaxpr) -> List[str]:
        names: List[str] = []
        for eqn in jaxpr.eqns:
            if "pallas_call" in eqn.primitive.name:
                names.append(str(eqn.params["name"]))
            for sj in sub_jaxprs(eqn.params):
                names += walk(sj)
        return names

    return walk(jax.make_jaxpr(fn, **kwargs)(*args).jaxpr)
