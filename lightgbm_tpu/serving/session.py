"""ServingSession: pinned model + compiled-predictor cache + bucketing.

The reference's online-inference story is the single-row fast path
(``LGBM_BoosterPredictForMatSingleRowFastInit``, c_api.h:1399-1428): per-call
setup — config parsing, predictor construction — is hoisted out of the hot
loop into a reusable FastConfig. This module is that idea rebuilt for an
accelerator serving loop:

 * the packed tree arrays (models/predictor.py PackedModel) are built once
   per model version and, for the device engine, pinned in device memory
   once (``PackedModel.device_arrays``);
 * request batches are padded up to POWER-OF-TWO buckets, and the compiled
   scorer for each (model version, engine, bucket) is cached, so arbitrary
   request sizes hit a warm ``jit`` trace instead of recompiling —
   ``warmup()`` pre-compiles the whole bucket ladder before traffic lands;
 * with ``num_shards > 1`` the bucket is scored data-parallel over the
   existing ``parallel/`` mesh (rows sharded, model replicated — the
   inference twin of tree_learner=data).

Engines:

 * ``host``  — the PackedModel lockstep walk in f64 numpy. BIT-IDENTICAL
   to ``Booster.predict`` (same arrays, same arithmetic); the default on
   CPU backends and the universal fallback (linear leaves).
 * ``device`` — the jitted f32 lockstep walk (ops/predict.py
   predict_margin_packed) with f32-floored thresholds: rows route through
   the trees exactly like the host walk, but leaf-value accumulation is
   f32, so outputs agree to ~1e-6 relative, not bitwise (docs/SERVING.md).
 * ``binned`` — the bin-domain walk (ops/predict_binned.py): rows are
   binned ONCE through the model's frozen BinMappers, then scored with
   uint8 bin-index compares against bin-mapped thresholds — routing is
   exact by construction (split thresholds ARE bin upper bounds), so
   outputs are bit-identical to the f32 device walk, and the feature
   transfer shrinks 8x. Requires frozen mappers (in-process-trained
   models have them; pass ``bin_mappers=`` for loaded ones) — otherwise
   falls back to host loudly.
 * ``compiled`` — the binned walk, AOT-exported per bucket via
   ``jax.export`` and round-tripped through StableHLO serialization
   (export/compile.py roundtrip_binned_scorer): every score transits the
   exact executable bytes a ``task=convert_model`` artifact ships, so
   the in-process engine IS the artifact semantics. Same requirements
   and fallback as ``binned``; outputs bit-identical to it.
 * ``auto``  — device on TPU backends, host elsewhere.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..utils.log import log_info, log_warning
from .metrics import ServingMetrics


def bucket_for(n: int, min_bucket: int, max_bucket: int) -> int:
    """Smallest power-of-two >= n, clamped to [min_bucket, max_bucket]."""
    b = 1 << max(int(n) - 1, 0).bit_length()
    return max(min_bucket, min(b, max_bucket))


class CompiledPredictorCache:
    """(model version, engine, bucket) -> compiled scorer. Thread-safe;
    hit/miss counts feed the serving cache-hit-rate metric."""

    def __init__(self, metrics: Optional[ServingMetrics] = None) -> None:
        self._lock = threading.Lock()
        self._fns: Dict[Tuple, Callable] = {}
        self.hits = 0
        self.misses = 0
        self._metrics = metrics

    def get(self, key: Tuple, builder: Callable[[], Callable]) -> Callable:
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                self.hits += 1
                if self._metrics is not None:
                    self._metrics.record_cache(True)
                return fn
        # build OUTSIDE the lock (tracing/compiling can be slow); a rare
        # duplicate build is benign — last writer wins
        fn = builder()
        with self._lock:
            self._fns[key] = fn
            self.misses += 1
            if self._metrics is not None:
                self._metrics.record_cache(False)
        return fn

    def __len__(self) -> int:
        return len(self._fns)


class ServingSession:
    """One servable model version: immutable once constructed (hot-swap
    builds a NEW session, registry.py), safe to score from any thread."""

    def __init__(self, gbdt, *, engine: str = "auto",
                 max_batch: int = 1024, min_bucket: int = 8,
                 num_shards: int = 0, start_iteration: int = 0,
                 num_iteration: int = -1, warmup: bool = False,
                 metrics: Optional[ServingMetrics] = None,
                 version: int = 0, breaker=None, fault_plan=None,
                 profiler=None, bin_mappers=None,
                 binning_impl: str = "auto") -> None:
        self.gbdt = gbdt
        # graceful-degradation circuit breaker (serving/breaker.py):
        # guards the device scoring path; shared across hot-swapped
        # session versions so the degrade decision survives promotes
        self.breaker = breaker
        self.fault_plan = fault_plan
        # opt-in HBM watermark sampling per scored chunk (StageProfiler
        # .sample_hbm): how train+serve coexistence on one device is
        # profiled (task=online, docs/ONLINE.md); None costs one check
        self.profiler = profiler
        self._n_scored = 0              # chunk counter for fault hooks
        self.version = int(version)
        K = gbdt.num_tree_per_iteration
        total_iters = len(gbdt.models) // max(K, 1)
        end = total_iters if num_iteration <= 0 else min(
            total_iters, start_iteration + num_iteration)
        self._start = min(start_iteration, total_iters)
        self._end = max(end, self._start)
        self.K = K
        self.num_features = gbdt.max_feature_idx_ + 1
        # the FastInit analog: pack ONCE, reuse for every request (shares
        # the gbdt-level cache, so Booster.predict and the session pin
        # the SAME PackedModel)
        self._pm = gbdt._packed_model(self._start, self._end)
        self._avg_div = (self._end - self._start
                         if gbdt.average_output else 0)
        self._has_linear = any(getattr(t, "is_linear", False)
                               for t in gbdt.models)
        # frozen per-feature BinMappers for the binned engine: a freshly
        # trained gbdt carries its own (definitive); otherwise the
        # caller-provided set (carried across hot-swaps, registry.py)
        from ..ops.predict_binned import mappers_for
        derived = mappers_for(gbdt)
        self.bin_mappers = derived if derived is not None else bin_mappers
        self._bm = None

        self.max_batch = 1 << max(int(max_batch) - 1, 0).bit_length()
        self.requested_engine = engine
        self.engine = self._resolve_engine(engine)
        # raw-f32 fused serving (docs/PERF.md §8): a serve-mode device
        # bin table lets f32 requests bucketize IN the scoring launch —
        # no host bin_rows stage. Host/f64 requests are untouched.
        self.binning_impl = binning_impl
        self._bin_table = None
        self._raw_jit = None
        if self.engine in ("binned", "compiled"):
            from ..ops.bucketize import (BinningUnavailable,
                                         pack_bin_table,
                                         resolve_binning_impl)
            if resolve_binning_impl(binning_impl) == "device":
                try:
                    self._bin_table = pack_bin_table(
                        self._bm._mappers, mode="serve",
                        num_features=self._bm.num_features,
                        used_features=self._bm.used_features)
                except BinningUnavailable as e:
                    log_warning(f"serving: device binning unavailable "
                                f"({e}); f32 requests bin on host")
        self.metrics = metrics if metrics is not None else ServingMetrics(
            max_batch=self.max_batch)
        if self.metrics.max_batch == 0:
            self.metrics.max_batch = self.max_batch
        self._cache = CompiledPredictorCache(self.metrics)

        self.num_shards = 0
        self._mesh = None
        if num_shards > 1 and self.engine == "device":
            import jax
            avail = len(jax.devices())
            shards = 1 << (min(int(num_shards), avail).bit_length() - 1)
            if shards != num_shards:
                log_warning(f"serving num_shards={num_shards} rounded to "
                            f"{shards} (power of two, {avail} devices)")
            if shards > 1:
                from ..parallel import make_data_mesh
                self._mesh = make_data_mesh(shards)
                self.num_shards = shards
        elif num_shards > 1:
            log_warning(f"serving num_shards ignored on engine "
                        f"{self.engine!r}")
        self.min_bucket = bucket_for(
            max(int(min_bucket), self.num_shards or 1), 1, self.max_batch)
        self._lock = threading.Lock()
        self._device_jit = None
        self._binned_jit = None
        if warmup:
            self.warmup()

    # ------------------------------------------------------------------
    def _resolve_engine(self, engine: str) -> str:
        if engine not in ("auto", "host", "device", "binned", "compiled"):
            raise ValueError(f"unknown serving engine {engine!r}")
        if engine == "host":
            return "host"
        if engine in ("binned", "compiled"):
            from ..ops.predict_binned import (BinnedUnavailable,
                                              build_binned_model)
            try:
                self._bm = build_binned_model(self._pm, self.bin_mappers)
                return engine
            except BinnedUnavailable as e:
                log_warning(f"serving: {engine} engine unavailable ({e}); "
                            f"falling back to host")
                return "host"
        if self._has_linear:
            # graceful fallback: linear leaves only exist on the host
            # paths (tree.cpp AddPredictionToScore linear path)
            if engine == "device":
                log_warning("serving: model has linear leaves; device "
                            "engine unavailable, falling back to host")
            return "host"
        if engine == "device":
            return "device"
        import jax
        return "device" if jax.default_backend() == "tpu" else "host"

    # ------------------------------------------------------------------
    @classmethod
    def from_booster(cls, booster, **kwargs) -> "ServingSession":
        """Mirror Booster.predict's iteration default: best_iteration
        when early stopping picked one."""
        if "num_iteration" not in kwargs:
            bi = getattr(booster, "best_iteration", -1)
            kwargs["num_iteration"] = bi if bi and bi > 0 else -1
        return cls(booster._gbdt, **kwargs)

    @classmethod
    def from_model_string(cls, model_str: str, **kwargs) -> "ServingSession":
        from ..models.gbdt import GBDT
        return cls(GBDT.load_model_from_string(model_str), **kwargs)

    @classmethod
    def from_file(cls, path: str, **kwargs) -> "ServingSession":
        with open(path) as f:
            return cls.from_model_string(f.read(), **kwargs)

    # ------------------------------------------------------------------
    # compiled scorers
    # ------------------------------------------------------------------
    def _device_scorer(self, bucket: int) -> Callable:
        """Jitted f32 scorer for one padded bucket shape. All buckets
        share one jitted callable (jax keys traces by shape); the cache
        entry per bucket is what makes hit/miss == warm/cold trace."""
        if self._device_jit is None:
            import jax
            from ..ops.predict import predict_margin_packed
            pa = self._pm.device_arrays()
            K = self.K

            def score(Xp):                       # [b, F] f32 -> [K, b]
                return predict_margin_packed(pa, Xp, K)

            if self._mesh is not None:
                from ..parallel import build_sharded_score_fn
                self._device_jit = build_sharded_score_fn(self._mesh, score)
            else:
                self._device_jit = jax.jit(score)
        return self._device_jit

    def _binned_scorer(self, bucket: int) -> Callable:
        """Jitted bin-domain scorer: uint8 [b, F] bins -> [K, b] f32
        margins, bit-identical to the device f32 raw walk by
        construction (ops/predict_binned.py)."""
        if self._binned_jit is None:
            import jax
            from ..ops.predict_binned import predict_margin_binned
            pa = self._bm.device_arrays()
            K = self.K

            def score(Xp):                       # [b, F] u8 -> [K, b]
                return predict_margin_binned(pa, Xp, K)

            self._binned_jit = jax.jit(score)
        return self._binned_jit

    def _compiled_scorer(self, bucket: int) -> Callable:
        """Per-bucket AOT scorer: the binned walk exported via
        ``jax.export``, serialized, deserialized, and jitted — the
        in-process twin of a ``task=convert_model`` StableHLO artifact
        (export/compile.py). One executable per bucket shape (the
        artifact ladder), cached under (version, "compiled", bucket)."""
        from ..export.compile import roundtrip_binned_scorer
        return roundtrip_binned_scorer(self._bm, self.K, bucket)

    def _build_scorer(self, bucket: int) -> Callable:
        if self.engine == "device":
            return self._device_scorer(bucket)
        if self.engine == "binned":
            return self._binned_scorer(bucket)
        return self._compiled_scorer(bucket)

    def _raw_scorer(self, bucket: int) -> Callable:
        """Raw-f32 fused scorer: bucketize + bin-domain walk in ONE
        jitted launch — f32 [b, F] raw rows -> [K, b] margins with no
        host binning stage. Bit-identical to host bin_rows + the binned
        walk (the bucketize parity contract, ops/bucketize.py)."""
        if self.engine == "compiled":
            from ..export.compile import roundtrip_raw_scorer
            return roundtrip_raw_scorer(self._bm, self._bin_table,
                                        self.K, bucket)
        if self._raw_jit is None:
            import jax
            from ..ops.bucketize import bucketize_rows
            from ..ops.predict_binned import predict_margin_binned
            pa = self._bm.device_arrays()
            K = self.K
            t = self._bin_table

            def score(Xp):                   # [b, F] f32 raw -> [K, b]
                return predict_margin_binned(pa, bucketize_rows(Xp, t),
                                             K)

            self._raw_jit = jax.jit(score)
        return self._raw_jit

    def _scorer(self, kind: str, b: int) -> Callable:
        """Cached scorer for (``kind``, bucket) — ``kind`` is the engine
        name, plus ``_raw`` for the fused raw-f32 variant. An accelerator
        scorer is run once on zeros before it is cached, so a trace,
        lowering or compile error of the scorer raises HERE, on the
        caller's thread: score_margin's breaker guard covers scoring
        faults of a scorer that exists, never the lack of one."""
        def build():
            if kind == "host":
                # trivially warm closure over the packed model; rides
                # the same cache so hit-rate accounting is uniform
                return self._pm.predict_margin
            import jax
            raw = kind.endswith("_raw")
            fn = self._raw_scorer(b) if raw else self._build_scorer(b)
            zeros = (np.zeros((b, self.num_features), np.float32)
                     if raw or kind == "device" else
                     np.zeros((b, self._bm.num_features), np.uint8))
            jax.block_until_ready(fn(zeros))
            return fn
        return self._cache.get((self.version, kind, b), build)

    def warmup(self) -> List[int]:
        """Pre-compile the whole bucket ladder (min_bucket..max_batch,
        powers of two) before traffic lands, so no live request pays a
        compile. Returns the ladder."""
        ladder = []
        b = self.min_bucket
        while b <= self.max_batch:
            ladder.append(b)
            b *= 2
        for b in ladder:
            self._scorer(self.engine, b)
            if self._bin_table is not None:
                # warm the raw-f32 fused ladder alongside the uint8
                # one: live traffic may arrive either way
                self._scorer(self.engine + "_raw", b)
        log_info(f"serving warmup: engine={self.engine} "
                 f"buckets={ladder} shards={self.num_shards or 1}")
        return ladder

    # ------------------------------------------------------------------
    # scoring
    # ------------------------------------------------------------------
    def _score_device(self, fn: Callable, X: np.ndarray, c0: int,
                      c1: int, b: int) -> np.ndarray:
        import jax
        m = c1 - c0
        Xp = np.zeros((b, X.shape[1]), np.float32)
        Xp[:m] = X[c0:c1]
        return np.asarray(jax.device_get(fn(Xp)))[:, :m].astype(np.float64)

    def _score_binned(self, fn: Callable, X: np.ndarray, c0: int,
                      c1: int, b: int) -> np.ndarray:
        """Bin the chunk once through the frozen mappers (host-side
        searchsorted), then score uint8 bins on device — an 8x smaller
        transfer than the f32 path, bit-identical output."""
        import jax
        m = c1 - c0
        Xp = np.zeros((b, self._bm.num_features), np.uint8)
        if self.profiler is not None:
            with self.profiler.span("bin_rows"):
                Xp[:m] = self._bm.bin_rows(X[c0:c1])
            self.profiler.add_counter("bin_rows_rows", m)
            self.profiler.add_counter("bin_rows_bytes_in",
                                      X[c0:c1].nbytes)
            self.profiler.add_counter("bin_rows_bytes_out", Xp[:m].nbytes)
        else:
            Xp[:m] = self._bm.bin_rows(X[c0:c1])
        return np.asarray(jax.device_get(fn(Xp)))[:, :m].astype(np.float64)

    def _score_binned_raw(self, fn: Callable, X: np.ndarray, c0: int,
                          c1: int, b: int) -> np.ndarray:
        """Raw-f32 fused path: the chunk ships as f32 and the bucketize
        runs INSIDE the scoring launch (one program raw features ->
        margins; no host bin_rows stage, no separate binning launch)."""
        import jax
        m = c1 - c0
        Xp = np.zeros((b, self.num_features), np.float32)
        Xp[:m] = X[c0:c1, :self.num_features]
        if self.profiler is not None:
            self.profiler.add_counter("bin_rows_fused_rows", m)
            self.profiler.add_counter("bin_rows_fused_bytes_in",
                                      Xp[:m].nbytes)
        return np.asarray(jax.device_get(fn(Xp)))[:, :m].astype(np.float64)

    def score_margin(self, X: np.ndarray) -> np.ndarray:
        """[K, n] f64 raw margins for X [n, F] (f64 in, any request
        size: chunks of up to max_batch, each padded to its bucket).

        Engine degradation (docs/SERVING.md §Overload & SLOs): when a
        circuit breaker is attached and the engine is ``device`` (or
        ``binned``), each
        chunk first asks ``breaker.allow()`` — an OPEN breaker routes
        the chunk through the host walk (bit-identical to
        ``Booster.predict``, counted as ``host_fallbacks``) until a
        half-open probe succeeds. A device failure mid-chunk is recorded
        and the chunk is re-scored on the host, so a flaky device never
        surfaces as a client error while the host path works.

        f32 requests additionally keep their dtype when the session
        holds a device bin table: those chunks skip host binning and
        score through the fused bucketize+walk launch
        (``_score_binned_raw``), bit-identical to the f64 path."""
        X = np.asarray(X)
        raw_f32 = (X.dtype == np.float32 and self._bin_table is not None
                   and self.engine in ("binned", "compiled"))
        X = np.ascontiguousarray(X if raw_f32
                                 else np.asarray(X, np.float64))
        n = X.shape[0]
        out = np.empty((self.K, n), np.float64)
        for c0 in range(0, n, self.max_batch):
            c1 = min(c0 + self.max_batch, n)
            m = c1 - c0
            b = bucket_for(m, self.min_bucket, self.max_batch)
            seq, self._n_scored = self._n_scored, self._n_scored + 1
            # "device", "binned" and "compiled" are all accelerator
            # paths: breaker-guarded, host re-score on failure
            use_accel = self.engine in ("device", "binned", "compiled")
            if use_accel and self.breaker is not None \
                    and not self.breaker.allow():
                use_accel = False
                self.metrics.inc("host_fallbacks")
            t0 = time.perf_counter()
            if self.fault_plan is not None:
                # inside the timed region: the injected delay must show
                # up in batch latency (latency-SLO shed / breaker trip)
                self.fault_plan.slow_score(seq)
            if use_accel:
                # built + compiled OUTSIDE the guard (_scorer): a scorer
                # that cannot be built is an error, not a host chunk
                fn = self._scorer(
                    self.engine + ("_raw" if raw_f32 else ""), b)
                try:
                    if self.fault_plan is not None:
                        self.fault_plan.fail_score(seq)
                    if raw_f32:
                        r = self._score_binned_raw(fn, X, c0, c1, b)
                    elif self.engine == "device":
                        r = self._score_device(fn, X, c0, c1, b)
                    else:
                        r = self._score_binned(fn, X, c0, c1, b)
                    if self.breaker is not None:
                        self.breaker.record_success(
                            time.perf_counter() - t0)
                except Exception as e:
                    if self.breaker is not None:
                        self.breaker.record_failure(e)
                    self.metrics.inc("host_fallbacks")
                    log_warning(f"serving: {self.engine} scoring failed "
                                f"({e!r}); chunk re-scored on host")
                    r = self._scorer("host", b)(
                        np.asarray(X[c0:c1], np.float64))
            else:
                if self.fault_plan is not None:
                    self.fault_plan.fail_score(seq)
                # host path scores the exact rows (padding buys nothing
                # without a shaped trace) — bit-identical to
                # Booster.predict by construction; f32 raw chunks
                # upcast so the host walk always sees f64
                r = self._scorer("host", b)(
                    np.asarray(X[c0:c1], np.float64))
            self.metrics.record_batch(time.perf_counter() - t0, m)
            if self.profiler is not None:
                self.profiler.sample_hbm("serve_score")
            out[:, c0:c1] = r
        if self._avg_div:
            out /= self._avg_div
        return out

    def _postprocess(self, margins: np.ndarray,
                     raw_score: bool) -> np.ndarray:
        obj = self.gbdt.objective
        raw = margins
        if not raw_score and obj is not None and obj.need_convert_output:
            raw = obj.convert_output(raw)
        return raw[0] if raw.shape[0] == 1 else raw.T

    def predict(self, data, raw_score: bool = False) -> np.ndarray:
        """Score a batch; output shape/semantics match Booster.predict
        (and on the host engine, the VALUES match bitwise)."""
        from ..basic import _to_2d_numpy
        X = _to_2d_numpy(data)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        return self._postprocess(self.score_margin(X), raw_score)

    def predict_single(self, x, raw_score: bool = False) -> Any:
        """One-row host fast path (~depth lockstep [T] steps, the
        FastConfig single-row analog) — bypasses bucketing entirely; the
        universal fallback for models the device path can't serve."""
        t0 = time.perf_counter()
        out = self._pm.predict_single(
            np.asarray(x, np.float64).reshape(-1))
        if self._avg_div:
            out = out / self._avg_div
        self.metrics.record_batch(time.perf_counter() - t0, 1)
        out = self._postprocess(out[:, None], raw_score)
        return float(out[0]) if self.K == 1 else out[0]

    # ------------------------------------------------------------------
    def cache_info(self) -> Dict[str, Any]:
        return {"entries": len(self._cache), "hits": self._cache.hits,
                "misses": self._cache.misses, "engine": self.engine,
                "version": self.version,
                "device_binning": self._bin_table is not None,
                "num_shards": self.num_shards or 1}
