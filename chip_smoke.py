"""chip_smoke.py — the standing proof that train -> predict -> serve runs
on the chip, through the entry points a user calls, in ONE process.

    python chip_smoke.py

drives the main path at the width the repo benchmarks (bench.py:
Higgs-shaped, seeded synthetic, no network): ``lgb.Dataset(X f32
[4M x 28])`` (device binning) -> ``lgb.train`` (binary, 255 leaves, wave
grower, one batched 32-iteration scan chunk) at max_bin 63 AND 255 ->
``bst.predict`` on a 131k-row f32 batch (the device predictor) ->
``bst.serve(engine="binned")`` with warm-up, answering raw-f32 requests
of 1, 37 and 4096 rows plus a few ``predict_single`` calls. With four or
more devices it also trains the same data with ``tree_learner=data`` over
all of them and runs ``__graft_entry__.dryrun_multichip``.

It refuses to run without a known TPU (exit != 0, no result line), checks
results and not only liveness (train AUC above a CPU-recorded floor,
device binning == host BinMapper, device predict == host walk, serving
margins == ``bst.predict``), and proves the device did the work from
what the program exposes (Mosaic custom calls in the lowered train step,
``dispatch_count``, ``binned_on``, engine/fallback/breaker state). Any
failed check raises: nothing around a phase lets the script finish 0.
Phase wall times (cold = first call, compile included; warm = repeat),
with the backend-compile share of each, are printed as information; they
are not metrics. A second run against a warm compile-cache directory
shows the compile column collapse.

The last stdout line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.

``run(Sizes(...), Expect(...))`` is the test-only entry: tests/
test_chip_smoke.py drives the same phases at toy size on the CPU mesh,
with the expectations a CPU backend can meet, so the control flow is
exercised before chip time is spent.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time
from typing import Dict, NamedTuple, Tuple

import numpy as np

# switches that would take the device off the path being proven
_FORBIDDEN_ENV = ("LIGHTGBM_TPU_PALLAS_INTERPRET",
                  "LIGHTGBM_TPU_DISABLE_PALLAS",
                  "LIGHTGBM_TPU_DISABLE_DEVICE_BINNING",
                  "LIGHTGBM_TPU_DISABLE_BATCHED")


class Sizes(NamedTuple):
    """The run's shape. The defaults are the chip's; the CPU toy test
    passes its own."""
    rows: int = 4_000_000
    cols: int = 28
    leaves: int = 255
    iters: int = 32               # one full batched chunk
    max_bins: Tuple[int, ...] = (63, 255)
    predict_rows: int = 131_072   # >= 100k: the device predictor route
    serve_sizes: Tuple[int, ...] = (1, 37, 4096)
    serve_max_batch: int = 4096
    multichip: bool = True        # when >= 4 devices are visible
    seed: int = 42


class Expect(NamedTuple):
    """What the backend under the run must show. The defaults are the
    chip's; the CPU toy test passes its own."""
    binned_on: str = "device"
    custom_calls: bool = True     # Mosaic kernels in the lowered step
    device_predict: bool = True
    # train-AUC floors per max_bin after one chunk: a CPU run of this
    # script's data (same seed) at 200k rows / 32 iterations gave 0.9762
    # (63 bins) and 0.9778 (255 bins); train AUC falls as rows grow at a
    # fixed tree budget, hence the margin (a v5e gave 0.9588 and 0.9594
    # at the full 4M rows: my chip run, PR 22)
    auc_floor: Tuple[Tuple[int, float], ...] = ((63, 0.95), (255, 0.95))


def make_data(rows: int, cols: int, seed: int):
    """Seeded Higgs-shaped binary task; a smaller ``rows`` is a prefix of
    a larger one's features under the same weights."""
    rng = np.random.RandomState(seed)
    w = rng.normal(size=cols)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    noise = np.random.RandomState(seed + 1).normal(scale=0.5, size=rows)
    y = (X @ w + noise > 0).astype(np.float32)
    return X, y


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")
    print(f"  ok: {what}", flush=True)


class _Clock:
    """Phase wall times, and the part of each that JAX spent in the
    backend compiler (XLA + Mosaic, or the fetch from the persistent
    cache that replaces them), printed as a table at the end. The
    compile column is what a warm cache directory collapses; tracing and
    lowering are Python and stay."""

    _COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring
        self.rows = []
        self._compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event == self._COMPILE_EVENT:
            self._compile_s += secs

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def time(self, phase: str, fn):
        c0, t0 = self._compile_s, time.perf_counter()
        out = fn()
        dt, dc = time.perf_counter() - t0, self._compile_s - c0
        self.rows.append((phase, dt, dc))
        print(f"[{phase}] {dt:.2f} s (backend compile {dc:.2f} s)",
              flush=True)
        return out

    def report(self) -> None:
        print("phase wall times (information only):")
        print(f"  {'phase':<34s} {'wall':>9s}   {'of which backend compile':s}")
        for phase, dt, dc in self.rows:
            print(f"  {phase:<34s} {dt:9.2f} s {dc:9.2f} s")
        print(f"  {'total backend compile':<34s} {'':9s}   "
              f"{sum(r[2] for r in self.rows):9.2f} s")


def _train_auc(gbdt, y: np.ndarray, rows: int) -> float:
    """Train AUC from the device-resident scores with the framework's own
    tie-aware metric (as bench.py)."""
    from lightgbm_tpu.metrics import create_metric
    n = min(rows, 500_000)
    pred = np.asarray(gbdt.scores[0][:n])
    lab = y[:n]

    class _MD:
        label = lab
        weight = None
        query_boundaries = None

    m = create_metric("auc", gbdt.config)
    m.init(_MD(), lab.size)
    return float(m.eval(pred, None)[0][1])


def _custom_calls_in_step(gbdt) -> int:
    """``tpu_custom_call`` count in the lowered tree-grow step: lowering
    only (no compile, no execution) of the jitted function the scan body
    calls, on the trainer's own arrays."""
    import jax.numpy as jnp
    n = gbdt.scores.shape[1]
    F = len(gbdt.mappers)
    g = jnp.zeros((n,), jnp.float32)
    args = (gbdt.X_t, g, g, jnp.ones((n,), jnp.float32), gbdt.scores[0],
            jnp.float32(0.1), jnp.ones((F,), bool), jnp.int32(0), gbdt.meta)
    if gbdt.use_dist:
        lowered = gbdt._train_tree.lower(*args)
    else:
        lowered = gbdt._train_tree_core.lower(*args, None)
    return lowered.as_text().count("tpu_custom_call")


def _params(sz: Sizes, max_bin: int, **extra) -> Dict:
    # autotune stays off (the default): no home-directory decision file
    # may change which kernel runs
    return dict(objective="binary", num_leaves=sz.leaves,
                min_data_in_leaf=20, max_bin=max_bin, verbose=-1, **extra)


def train_phase(lgb, X, y, sz: Sizes, ex: Expect, clock: _Clock,
                max_bin: int, tag: str, **extra):
    params = _params(sz, max_bin, **extra)
    ds = lgb.Dataset(X, label=y, params=params)
    clock.time(f"{tag} dataset+bin", ds.construct)
    h = ds._handle
    _check(h.binned_on == ex.binned_on,
           f"{tag}: dataset binned on {h.binned_on!r}")
    k = min(sz.rows, 20_000)
    host = np.stack([m.value_to_bin(np.asarray(X[:k, orig], np.float64))
                     for m, orig in zip(h.mappers, h.real_feature_index)],
                    axis=1)
    _check(np.array_equal(host, h.X_binned[:k]),
           f"{tag}: binned matrix == host BinMapper on {k} rows")

    def cold():
        b = lgb.train(params, ds, num_boost_round=sz.iters)
        np.asarray(b._gbdt.scores[0][:1])       # wait for the device
        return b
    bst = clock.time(f"{tag} train cold ({sz.iters} it)", cold)
    g = bst._gbdt
    _check(g.grower == "wave", f"{tag}: grower is 'wave'")
    _check(g.iter == sz.iters and g.dispatch_count <= 2,
           f"{tag}: {g.iter} iterations in {g.dispatch_count} dispatch(es)")
    ncc = _custom_calls_in_step(g)
    _check((ncc > 0) == ex.custom_calls,
           f"{tag}: lowered train step holds {ncc} tpu_custom_call(s)")
    auc = _train_auc(g, y, sz.rows)
    floor = dict(ex.auc_floor)[max_bin]
    _check(np.isfinite(auc) and auc >= floor,
           f"{tag}: train AUC {auc:.5f} >= {floor}")

    def warm():
        bst.update_batch(sz.iters)
        np.asarray(g.scores[0][:1])
    clock.time(f"{tag} train warm ({sz.iters} it)", warm)
    _check(g.iter == 2 * sz.iters and g.dispatch_count <= 4,
           f"{tag}: second chunk reused the scan "
           f"({g.dispatch_count} dispatches total)")
    return bst, auc


def predict_phase(bst, X, sz: Sizes, ex: Expect, clock: _Clock, tag: str):
    g = bst._gbdt
    Xp = X[:sz.predict_rows]
    dev = clock.time(f"{tag} predict cold ({len(Xp)} rows)",
                     lambda: bst.predict(Xp, raw_score=True))
    took_device = getattr(g, "_device_tables_cache", None) is not None
    _check(took_device == ex.device_predict,
           f"{tag}: predict took the "
           f"{'device' if took_device else 'host'} route")
    _check(dev.shape == (len(Xp),) and bool(np.isfinite(dev).all()),
           f"{tag}: predictions finite, shape {dev.shape}")
    k = min(len(Xp), 20_000)               # < 100k rows: the host walk
    host = bst.predict(Xp[:k], raw_score=True)
    err = float(np.max(np.abs(dev[:k] - host)))
    _check(np.allclose(dev[:k], host, rtol=1e-5, atol=1e-5),
           f"{tag}: predict == host walk on {k} rows (max |d| {err:.2e})")
    clock.time(f"{tag} predict warm",
               lambda: bst.predict(Xp, raw_score=True))


def serve_phase(bst, X, sz: Sizes, ex: Expect, clock: _Clock, tag: str):
    from lightgbm_tpu.serving.breaker import CircuitBreaker
    breaker = CircuitBreaker()
    sess = clock.time(f"{tag} serve build+warmup", lambda: bst.serve(
        engine="binned", warmup=True, max_batch=sz.serve_max_batch,
        breaker=breaker))
    _check(sess.engine == "binned", f"{tag}: serving engine is 'binned'")
    fused = sess.cache_info()["device_binning"]
    _check(fused == (ex.binned_on == "device"),
           f"{tag}: raw-f32 requests bucketize "
           f"{'in the scoring launch' if fused else 'on the host'}")
    misses0 = sess.cache_info()["misses"]

    def requests():
        off = 0
        for n in sz.serve_sizes:
            q = np.ascontiguousarray(X[off:off + n])          # raw f32
            off += n
            got = sess.predict(q, raw_score=True)
            ref = bst.predict(q, raw_score=True)
            _check(np.allclose(got, ref, rtol=1e-5, atol=1e-5),
                   f"{tag}: served margins == bst.predict ({n} rows)")
        for i in range(3):
            got = sess.predict_single(X[i], raw_score=True)
            ref = float(bst.predict(X[i:i + 1], raw_score=True)[0])
            _check(abs(got - ref) <= 1e-5 * (1 + abs(ref)),
                   f"{tag}: predict_single == bst.predict (row {i})")
    clock.time(f"{tag} serve requests", requests)
    _check(sess.cache_info()["misses"] == misses0,
           f"{tag}: every request hit a warm scorer")
    _check(sess.metrics.counters["host_fallbacks"] == 0,
           f"{tag}: host_fallbacks == 0")
    _check(breaker.state == "closed" and breaker.trips == 0,
           f"{tag}: breaker closed, never tripped")


def multichip_phase(lgb, X, y, sz: Sizes, ex: Expect, clock: _Clock,
                    auc_one_chip: float, n_dev: int) -> None:
    max_bin = sz.max_bins[0]
    tag = f"dp{n_dev} bin{max_bin}"
    bst, auc = train_phase(lgb, X, y, sz, ex, clock, max_bin, tag,
                           tree_learner="data")
    g = bst._gbdt
    _check(g.use_dist and g.n_shards == n_dev,
           f"{tag}: n_shards == {g.n_shards}")
    for name, arr in (("binned matrix", g.X_t), ("scores", g.scores)):
        devs = {s.device for s in arr.addressable_shards}
        _check(len(devs) == n_dev,
               f"{tag}: {name} sharded over {len(devs)} distinct devices")
    mode = g._comm_profile["comm_mode"]
    _check(g.grow_cfg.parallel_hist_mode == "auto"
           and mode == "reduce_scatter" and g._collective_failures == 0,
           f"{tag}: histogram exchange stayed {mode!r} (no degrade)")
    _check(abs(auc - auc_one_chip) <= 2e-3,
           f"{tag}: AUC {auc:.5f} matches one chip's {auc_one_chip:.5f}")
    import __graft_entry__
    clock.time(f"dryrun_multichip({n_dev})",
               lambda: __graft_entry__.dryrun_multichip(n_dev))


def run(sz: Sizes, ex: Expect) -> None:
    """All phases; raises on the first failed check."""
    import jax

    import lightgbm_tpu as lgb
    clock = _Clock()
    try:
        _phases(jax, lgb, sz, ex, clock)
    finally:
        clock.close()
    clock.report()


def _phases(jax, lgb, sz: Sizes, ex: Expect, clock: _Clock) -> None:
    X, y = clock.time("make data", lambda: make_data(sz.rows, sz.cols,
                                                     sz.seed))
    aucs = {}
    for max_bin in sz.max_bins:
        tag = f"bin{max_bin}"
        bst, aucs[max_bin] = train_phase(lgb, X, y, sz, ex, clock, max_bin,
                                         tag)
        predict_phase(bst, X, sz, ex, clock, tag)
        serve_phase(bst, X, sz, ex, clock, tag)
        del bst
    n_dev = jax.device_count()
    if sz.multichip and n_dev >= 4:
        multichip_phase(lgb, X, y, sz, ex, clock, aucs[sz.max_bins[0]],
                        n_dev)
    else:
        print(f"multichip phase NOT run: {n_dev} device(s) visible, "
              f"multichip={sz.multichip} (needs >= 4 devices)", flush=True)


def main() -> int:
    set_env = [k for k in _FORBIDDEN_ENV if os.environ.get(k)]
    if set_env:
        raise SystemExit(f"chip_smoke: unset {set_env}: they take the "
                         "device off the path under test")
    import jax
    import jaxlib

    from lightgbm_tpu.runtime.device import require_tpu
    device = require_tpu()          # RuntimeError -> exit 1, no result
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"device: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']} | "
          f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
          f"libtpu {libtpu} | compile cache: "
          f"{jax.config.jax_compilation_cache_dir}", flush=True)
    # native/lgbtpu_native.so is git-ignored and rebuilt from loader.cpp
    # on demand; nothing below needs it (it serves text parsing and host
    # binning of >= 65536-row columns, and this path bins on the device)
    print("native loader: not on this path; g++ "
          + ("found" if shutil.which("g++") else
             "ABSENT (text parsing / host binning fall back to NumPy)"),
          flush=True)
    run(Sizes(), Expect())
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
