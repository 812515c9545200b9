"""Init-time strategy autotuning via short timed probes.

The reference picks its histogram layout by measurement, not heuristics:
``TrainingShareStates::CalcBinOffsets``/``InitTrain`` times row-wise vs
col-wise histogram construction on the real data and locks in the faster
one (src/io/train_share_states.cpp). This module is the same timing
dance for the TPU build's real degrees of freedom:

 * which grower strategy — ``wave`` (ops/grow_wave.py), ``compact``
   (ops/grow_fast.py), ``masked`` (ops/grow.py) — by growing one probe
   tree per candidate on a row subsample of the REAL binned matrix with
   synthetic gradients from a fixed seed;
 * the histogram chunk layout (``rows_per_chunk``) by timing
   ``build_histogram`` at candidate chunk sizes;
 * the histogram implementation (``legacy`` uniform kernel vs the
   bin-width-tiered ``tiered``/``tiered_hilo`` paths of
   ops/histogram_tiered.py — see docs/PERF.md) by timing
   ``build_histogram`` per candidate, only when config left
   ``histogram_impl=auto``.

Decisions are cached in-process and on disk, keyed by
(n_rows, n_features, max_bin, num_leaves, device kind) — the shape
signature that determines kernel behavior (bin width, row count and
feature count pick the one-hot decomposition; docs/PERF.md documents
the key layout), so a rerun of the same workload skips the probes
entirely.

Determinism: probe gradients come from a fixed ``seed`` and the timing
clock is injectable (``timer``), so tests can force exact tie-breaks.
Ties within ``TIE_TOL`` resolve by ``AUTOTUNE_PREFERENCE`` order, which
matches the hard-coded ladder's ordering — a tie reproduces the ladder.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

# ladder order (models/gbdt.py grower selection): on a timing tie the
# autotuner must agree with the memory ladder's preference
AUTOTUNE_PREFERENCE = ("wave", "wave_exact", "compact", "masked")

# two timings within 2% are a tie (probe noise floor)
TIE_TOL = 0.02

DEFAULT_PROBE_ROWS = 65536
CHUNK_CANDIDATES = (4096, 8192, 32768)

# data-parallel histogram exchange candidates (ops/grow.py,
# docs/PERF.md §Communication); on a tie prefer reduce_scatter — it is
# the wire-cheaper mode ((k-1)/k vs 2(k-1)/k bytes) and produces
# bit-identical trees, so the tie-break only affects the wire profile
COMM_MODE_PREFERENCE = ("reduce_scatter", "allreduce")

# histogram implementation candidates (ops/histogram.py _tier_route,
# docs/PERF.md); tie preference matches the "auto" default so a tie
# reproduces untuned behavior — the row-wise layouts probe last and must
# win outright (the TrainingShareStates col-vs-row timing dance,
# train_share_states.cpp InitTrain). "rowwise_packed" is the 4-bit
# nibble pack (histogram_rowwise.py Pack4Plan); its probe silently runs
# plain rowwise when nothing is packable, so it never wins a tie.
HIST_IMPL_CANDIDATES = ("tiered_hilo", "tiered", "legacy", "rowwise",
                        "rowwise_packed")
# force_col_wise restricts the probe to these (models/gbdt.py)
COL_WISE_HIST_IMPLS = ("tiered_hilo", "tiered", "legacy")

# in-process decision cache: key -> decision dict
_MEM_CACHE: Dict[str, Dict[str, Any]] = {}


def make_key(n_rows: int, n_features: int, max_bin: int, num_leaves: int,
             device_kind: str = "") -> str:
    """Cache key over the shape signature that determines kernel choice."""
    if not device_kind:
        import jax
        device_kind = jax.local_devices()[0].device_kind
    dk = str(device_kind).replace(" ", "_")
    return f"r{int(n_rows)}_f{int(n_features)}_b{int(max_bin)}" \
           f"_l{int(num_leaves)}_{dk}"


def default_cache_path() -> str:
    env = os.environ.get("LIGHTGBM_TPU_AUTOTUNE_CACHE", "")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache",
                        "lightgbm_tpu", "autotune.json")


def load_disk_cache(path: str) -> Dict[str, Dict[str, Any]]:
    try:
        with open(path) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except Exception:
        return {}


def save_disk_cache(path: str, cache: Dict[str, Dict[str, Any]]) -> None:
    try:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(cache, f, indent=2, sort_keys=True)
        os.replace(tmp, path)
    except Exception:
        pass   # a cold cache next run, never a training failure


def _grower_fn(name: str):
    if name in ("wave", "wave_exact"):
        from ..ops.grow_wave import grow_tree_wave
        return grow_tree_wave, True
    if name == "compact":
        from ..ops.grow_fast import grow_tree_fast
        return grow_tree_fast, False
    from ..ops.grow import grow_tree
    return grow_tree, False


def _block(out) -> None:
    import jax
    jax.tree_util.tree_map(
        lambda x: x.block_until_ready()
        if hasattr(x, "block_until_ready") else x, out)


def _best_of_2(jitted, args, timer: Callable[[], float]) -> float:
    """Compile + warm once, then the faster of two fenced runs."""
    from .profiler import device_barrier
    _block(jitted(*args))
    best = float("inf")
    for _ in range(2):
        device_barrier()
        t0 = timer()
        _block(jitted(*args))
        best = min(best, timer() - t0)
    return best


def probe_strategies(X_t, meta, cfg, candidates: Sequence[str],
                     probe_rows: int = DEFAULT_PROBE_ROWS, seed: int = 0,
                     timer: Callable[[], float] = time.perf_counter,
                     ) -> Dict[str, float]:
    """Grow one probe tree per candidate grower on a row subsample of the
    real binned matrix; return {candidate: best_of_2_seconds}.

    Gradients are synthetic (fixed ``seed``, binary-like: uniform grad in
    [-0.5, 0.5), constant hessian 0.25) so the probe exercises the real
    split math without touching training state. A candidate that fails
    to compile or run raises: a probe returns timings, never a verdict on
    what works.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = int(X_t.shape[1])
    m = max(min(int(probe_rows), n), 1)
    Xs = jnp.asarray(jax.device_get(X_t[:, :m]))
    rng = np.random.RandomState(seed)
    g = jnp.asarray(rng.uniform(-0.5, 0.5, size=m).astype(np.float32))
    h = jnp.full((m,), 0.25, jnp.float32)
    bag = jnp.ones((m,), jnp.float32)

    timings: Dict[str, float] = {}
    for name in candidates:
        grow_fn, takes_seed = _grower_fn(name)
        cfg_c = cfg._replace(wave_exact=(name == "wave_exact"))

        def run(X, gg, hh, bb, mt, _fn=grow_fn, _cfg=cfg_c,
                _seed=takes_seed):
            kw = {"rng_seed": jnp.int32(seed)} if _seed else {}
            return _fn(X, gg, hh, bb, mt, _cfg, **kw)

        # meta is an argument, as in the trainer's own programs
        timings[name] = _best_of_2(jax.jit(run), (Xs, g, h, bag, meta),
                                   timer)
    return timings


def probe_rows_per_chunk(X_t, cfg, chunk_candidates: Sequence[int]
                         = CHUNK_CANDIDATES,
                         probe_rows: int = DEFAULT_PROBE_ROWS,
                         seed: int = 0,
                         timer: Callable[[], float] = time.perf_counter,
                         ) -> Dict[int, float]:
    """Time ``build_histogram`` at candidate chunk sizes on the real
    binned subsample (the direct analog of the reference's row-wise vs
    col-wise layout timing)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops.histogram import build_histogram

    n = int(X_t.shape[1])
    m = max(min(int(probe_rows), n), 1)
    Xs = jnp.asarray(jax.device_get(X_t[:, :m]))
    rng = np.random.RandomState(seed)
    vals = jnp.asarray(                                     # [2, N]
        rng.uniform(-0.5, 0.5, size=(2, m)).astype(np.float32))
    B = int(cfg.num_bins_padded)

    timings: Dict[int, float] = {}
    for rc in chunk_candidates:
        rc = int(rc)

        def run(X, v, _rc=rc):
            return build_histogram(X, v, B, rows_per_chunk=_rc)

        timings[rc] = _best_of_2(jax.jit(run), (Xs, vals), timer)
    return timings


def probe_hist_impls(X_t, cfg, impl_candidates: Sequence[str]
                     = HIST_IMPL_CANDIDATES,
                     probe_rows: int = DEFAULT_PROBE_ROWS,
                     seed: int = 0,
                     timer: Callable[[], float] = time.perf_counter,
                     num_slots: int = 8,
                     ) -> Dict[str, float]:
    """Time the WAVE-shaped histogram (``build_histogram_slots`` at
    ``num_slots`` slots) per implementation candidate on the real binned
    subsample (docs/PERF.md): the col-wise kernels (legacy uniform,
    bin-width-tiered, hi/lo wide-bin variant) vs the row-wise
    multi-value layout — the ``TrainingShareStates::InitTrain``
    col-vs-row timing probe, run on device instead of estimated from
    sparsity. The slot-shaped probe matters for the row-wise layouts:
    their multi-value advantage (and their VMEM eligibility) scales with
    the wave slot count, so a K=1 root-histogram probe both underrates
    them and can pin a layout the wave dispatcher would silently fall
    back from. Candidates whose dispatcher route would NOT actually run
    at this slot count (``rowwise_eligible``) are dropped instead of
    timing their fallback under the wrong label. Uses ``cfg.hist_tiers``
    — callers gate on it being set; ``impl_candidates`` narrows the
    field (``force_col_wise`` passes ``COL_WISE_HIST_IMPLS``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops.histogram import _tier_route, build_histogram_slots

    n = int(X_t.shape[1])
    m = max(min(int(probe_rows), n), 1)
    K = max(int(num_slots), 1)
    Xs = jnp.asarray(jax.device_get(X_t[:, :m]))
    rng = np.random.RandomState(seed)
    vals = jnp.asarray(
        rng.uniform(-0.5, 0.5, size=(2, m)).astype(np.float32))
    slot = jnp.asarray(rng.randint(0, K, size=m).astype(np.int32))
    B = int(cfg.num_bins_padded)
    tiers = tuple(int(t) for t in cfg.hist_tiers)

    timings: Dict[str, float] = {}
    for impl in impl_candidates:
        if impl in ("rowwise", "rowwise_packed"):
            from ..ops.histogram_rowwise import rowwise_eligible
            route = _tier_route(tiers, int(Xs.shape[0]), B, impl)
            if route is None \
                    or route[0] not in ("rowwise", "rowwise_packed") \
                    or not rowwise_eligible(route[1], 2, K):
                continue      # dispatcher would fall back col-wise

        def run(X, v, s, _impl=impl):
            return build_histogram_slots(X, v, s, K, B,
                                         rows_per_chunk=cfg.rows_per_chunk,
                                         tiers=tiers, impl=_impl)

        timings[impl] = _best_of_2(jax.jit(run), (Xs, vals, slot), timer)
    return timings


def probe_comm_modes(mesh, n_features: int, num_bins_padded: int,
                     channels: int = 3, seed: int = 0,
                     timer: Callable[[], float] = time.perf_counter,
                     ) -> Dict[str, float]:
    """Time the two histogram-exchange collectives on the REAL mesh:
    one full-buffer ``psum`` (allreduce) vs one ``psum_scatter`` over the
    feature-padded axis (reduce_scatter), at the exact per-leaf payload
    shape the growers exchange ([C, F_pad, B], docs/PERF.md
    §Communication). Unlike the grower/layout probes this one needs a
    multi-device mesh, so it runs where those are skipped (models/gbdt.py
    gates the call on ``use_dist``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from ..parallel.context import DATA_AXIS, DistContext

    k = int(mesh.devices.size)
    dist = DistContext(DATA_AXIS)
    Fh = max(-(-int(n_features) // k) * k, k)
    B = max(int(num_bins_padded), 8)
    rng = np.random.RandomState(seed)
    buf = jnp.asarray(rng.uniform(-1.0, 1.0,
                                  size=(channels, Fh, B)).astype(np.float32))

    candidates = {
        "allreduce": (lambda x: dist.psum(x), P()),
        "reduce_scatter": (lambda x: dist.psum_scatter(x, axis=1),
                           P(None, DATA_AXIS, None)),
    }
    timings: Dict[str, float] = {}
    for name, (fn, out_spec) in candidates.items():
        jitted = jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P(),), out_specs=out_spec,
            check_vma=False))
        timings[name] = _best_of_2(jitted, (buf,), timer)
    return timings


def autotune_comm_decision(mesh, *, n_rows: int, n_features: int,
                           max_bin: int, num_leaves: int,
                           num_bins_padded: int, channels: int = 3,
                           cache_path: str = "", seed: int = 0,
                           timer: Callable[[], float] = time.perf_counter,
                           ) -> Dict[str, Any]:
    """Resolve ``parallel_hist_mode=auto`` for a data-parallel run by a
    timed probe, cached like the grower decision. The cache key is the
    standard shape signature plus the mesh size (the collective's cost
    depends on how many ranks the payload crosses, not just its shape).

    Returns ``{"parallel_hist_mode", "comm_timings", "key", "cached"}``;
    ``parallel_hist_mode`` is None when both probes failed (caller keeps
    the grower's default exchange)."""
    k = int(mesh.devices.size)
    key = make_key(n_rows, n_features, max_bin, num_leaves) + f"_mesh{k}"
    if key in _MEM_CACHE:
        return dict(_MEM_CACHE[key], cached="memory")
    path = cache_path or default_cache_path()
    disk = load_disk_cache(path)
    hit = disk.get(key)
    if isinstance(hit, dict) and hit.get("parallel_hist_mode") in (
            None, *COMM_MODE_PREFERENCE):
        _MEM_CACHE[key] = hit
        return dict(hit, cached="disk")

    timings = probe_comm_modes(mesh, n_features, num_bins_padded,
                               channels=channels, seed=seed, timer=timer)
    mode = _pick_winner(timings, COMM_MODE_PREFERENCE)
    decision: Dict[str, Any] = {
        "parallel_hist_mode": mode,
        "comm_timings": {n: round(v, 6) for n, v in timings.items()},
        "key": key,
        "mesh_size": k,
    }
    _MEM_CACHE[key] = decision
    disk[key] = decision
    save_disk_cache(path, disk)
    return dict(decision, cached=False)


def pin_comm_decision(*, n_rows: int, n_features: int, max_bin: int,
                      num_leaves: int, mesh_size: int, mode: str,
                      cache_path: str = "", reason: str = "",
                      ) -> Dict[str, Any]:
    """Overwrite the cached comm decision with a forced ``mode`` under
    the same key ``autotune_comm_decision`` reads. The training
    watchdog's reduce_scatter -> allreduce degrade calls this to POISON
    the broken mode (models/gbdt.py _degrade_comm_mode): the very next
    run of the same shape/mesh starts on the safe exchange instead of
    re-discovering the failure. Both exchanges produce bit-identical
    trees, so pinning only changes the wire profile."""
    key = make_key(n_rows, n_features, max_bin, num_leaves) \
        + f"_mesh{int(mesh_size)}"
    decision: Dict[str, Any] = {
        "parallel_hist_mode": str(mode),
        "key": key,
        "mesh_size": int(mesh_size),
        "pinned": True,
        "reason": str(reason),
    }
    _MEM_CACHE[key] = decision
    path = cache_path or default_cache_path()
    disk = load_disk_cache(path)
    disk[key] = decision
    save_disk_cache(path, disk)
    return decision


def probe_binning(mappers, *, probe_rows: int = 16384, seed: int = 0,
                  timer: Callable[[], float] = time.perf_counter,
                  ) -> Dict[str, float]:
    """Time the two value->bin arms on synthetic f32 rows from a fixed
    seed: ``host`` is the per-feature numpy ``value_to_bin`` loop every
    host site runs, ``device`` is the packed-table bucketize
    (ops/bucketize.py) as one jitted launch. Both arms bin the same
    rows; the device arm is bit-identical by construction, so the probe
    only decides where the work runs. Returns an empty dict (caller
    keeps the untuned default) when the mapper set is not
    device-packable."""
    import numpy as np

    from ..ops.bucketize import (BinningUnavailable, _bin_rows_jit,
                                 pack_bin_table)

    try:
        table = pack_bin_table(mappers, mode="train")
    except BinningUnavailable:
        return {}
    rng = np.random.RandomState(seed)
    n = max(int(probe_rows), 256)
    X = rng.uniform(-100.0, 100.0,
                    size=(n, len(mappers))).astype(np.float32)

    timings: Dict[str, float] = {}

    def host_arm() -> None:
        for f, m in enumerate(mappers):
            if m is not None and not getattr(m, "is_trivial", False):
                m.value_to_bin(np.asarray(X[:, f], np.float64))

    best = float("inf")
    host_arm()                                     # warm numpy caches
    for _ in range(2):
        t0 = timer()
        host_arm()
        best = min(best, timer() - t0)
    timings["host"] = best
    timings["device"] = _best_of_2(       # the ingest program itself
        _bin_rows_jit(), (X, table.table, table.cat_val, table.meta), timer)
    return timings


def autotune_binning_decision(mappers, *, n_rows: int, n_features: int,
                              max_bin: int, num_leaves: int,
                              cache_path: str = "", seed: int = 0,
                              timer: Callable[[], float]
                              = time.perf_counter,
                              ) -> Dict[str, Any]:
    """Resolve ``binning_impl=auto`` by a timed probe, cached under the
    standard shape key with a ``_binning`` suffix. On a tie the
    backend's untuned "auto" resolution wins, so a tie reproduces
    untuned behavior (the histogram-impl contract). Returns
    ``{"binning_impl", "binning_timings", "key", "cached"}``;
    ``binning_impl`` is None when both arms failed or the mapper set is
    not packable (caller falls back to the host path)."""
    from ..ops.bucketize import resolve_binning_impl

    key = make_key(n_rows, n_features, max_bin, num_leaves) + "_binning"
    if key in _MEM_CACHE:
        return dict(_MEM_CACHE[key], cached="memory")
    path = cache_path or default_cache_path()
    disk = load_disk_cache(path)
    hit = disk.get(key)
    if isinstance(hit, dict) and hit.get("binning_impl") in (
            None, "host", "device"):
        _MEM_CACHE[key] = hit
        return dict(hit, cached="disk")

    timings = probe_binning(mappers, seed=seed, timer=timer)
    default = resolve_binning_impl("auto")
    preference = (default, "host" if default == "device" else "device")
    impl = _pick_winner(timings, preference)
    decision: Dict[str, Any] = {
        "binning_impl": impl,
        "binning_timings": {n: round(v, 6) for n, v in timings.items()},
        "key": key,
    }
    _MEM_CACHE[key] = decision
    disk[key] = decision
    save_disk_cache(path, disk)
    return dict(decision, cached=False)


def _pick_winner(timings: Dict[str, float],
                 preference: Sequence[str]) -> Optional[str]:
    """Fastest candidate; ties within TIE_TOL resolve by preference
    order (then by insertion order for unlisted names)."""
    if not timings:
        return None
    t_best = min(timings.values())
    tied = [k for k, v in timings.items() if v <= t_best * (1.0 + TIE_TOL)]

    def rank(name: str) -> int:
        try:
            return preference.index(name)
        except ValueError:
            return len(preference) + list(timings).index(name)

    return min(tied, key=rank)


def autotune_decision(X_t, meta, cfg, candidates: Sequence[str], *,
                      n_rows: int, n_features: int, max_bin: int,
                      num_leaves: int, cache_path: str = "",
                      probe_rows: int = DEFAULT_PROBE_ROWS, seed: int = 0,
                      timer: Callable[[], float] = time.perf_counter,
                      tune_chunks: bool = True,
                      hist_impl_candidates: Optional[Sequence[str]] = None,
                      ) -> Dict[str, Any]:
    """Full decision: cached if seen, otherwise probe and cache.

    Returns ``{"grower", "rows_per_chunk", "timings", "chunk_timings",
    "key", "probe_rows", "cached"}``. ``grower`` is None when every
    probe failed (caller keeps its ladder choice).
    ``hist_impl_candidates`` restricts the histogram-layout probe (e.g.
    COL_WISE_HIST_IMPLS under force_col_wise); None = all candidates.
    """
    impl_cands = tuple(hist_impl_candidates or HIST_IMPL_CANDIDATES)
    # a cached entry naming any other impl (a cache file is input from
    # outside the program) reads as a miss and is probed again
    impl_ok = (None, *impl_cands)
    key = make_key(n_rows, n_features, max_bin, num_leaves)
    if key in _MEM_CACHE \
            and _MEM_CACHE[key].get("hist_impl") in impl_ok:
        return dict(_MEM_CACHE[key], cached="memory")
    path = cache_path or default_cache_path()
    disk = load_disk_cache(path)
    hit = disk.get(key)
    if isinstance(hit, dict) and hit.get("grower") in (None, *candidates) \
            and hit.get("hist_impl") in impl_ok:
        _MEM_CACHE[key] = hit
        return dict(hit, cached="disk")

    timings = probe_strategies(X_t, meta, cfg, candidates,
                               probe_rows=probe_rows, seed=seed, timer=timer)
    winner = _pick_winner(timings, AUTOTUNE_PREFERENCE)

    chunk_timings: Dict[int, float] = {}
    rows_per_chunk = int(cfg.rows_per_chunk)
    if tune_chunks:
        cands = sorted({*CHUNK_CANDIDATES, rows_per_chunk})
        chunk_timings = probe_rows_per_chunk(
            X_t, cfg, cands, probe_rows=probe_rows, seed=seed, timer=timer)
        if chunk_timings:
            # prefer the configured chunk size on a tie (stable jit keys)
            pref = [str(rows_per_chunk)] + [str(c) for c in cands]
            best = _pick_winner(
                {str(k): v for k, v in chunk_timings.items()}, pref)
            if best is not None:
                rows_per_chunk = int(best)

    # histogram implementation: probed only when config left the choice
    # open (histogram_impl=auto) and the dataset published its tier table
    hist_impl: Optional[str] = None
    hist_impl_timings: Dict[str, float] = {}
    if getattr(cfg, "hist_impl", "auto") == "auto" \
            and getattr(cfg, "hist_tiers", ()):
        hist_impl_timings = probe_hist_impls(
            X_t, cfg, impl_candidates=impl_cands,
            probe_rows=probe_rows, seed=seed, timer=timer)
        hist_impl = _pick_winner(hist_impl_timings, HIST_IMPL_CANDIDATES)

    decision: Dict[str, Any] = {
        "grower": winner,
        "rows_per_chunk": rows_per_chunk,
        "hist_impl": hist_impl,
        "timings": {k: round(v, 6) for k, v in timings.items()},
        "chunk_timings": {str(k): round(v, 6)
                          for k, v in chunk_timings.items()},
        "hist_impl_timings": {k: round(v, 6)
                              for k, v in hist_impl_timings.items()},
        "key": key,
        "probe_rows": min(int(probe_rows), int(X_t.shape[1])),
    }
    _MEM_CACHE[key] = decision
    disk[key] = decision
    save_disk_cache(path, disk)
    return dict(decision, cached=False)
