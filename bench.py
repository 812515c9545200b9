"""Benchmark: single-chip training throughput on a Higgs-like binary task.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"train_auc", "device"} — ``device`` is the platform, device_kind and
count JAX reports. The run refuses to start without a known TPU
(``runtime/device.py:require_tpu``): a rate from a CPU fallback is not
this metric.

Baseline: the reference's published CPU Higgs number — 10.5M train rows x
500 iterations in 130.094 s on 2x E5-2690 v4 (docs/Experiments.rst:113,
BASELINE.md) = 4.04e7 row-iterations/s. vs_baseline > 1 means this TPU
build trains faster than the reference's 28-thread CPU run.

Config mirrors the reference's own accelerator methodology
(docs/GPU-Performance.rst:160-171): binary objective, 255 leaves, and
max_bin=63 on the device — the reference benchmarks its GPU learner at
63 bins against the 255-bin CPU run, noting "Minimal impact on AUC" and
that small bins are where accelerator histograms pay off. The 255-bin
device path is also supported (BENCH_BIN=255); AUC parity for both bin
widths is gated by tests/test_reference_parity.py. Rows/features/iters
scale via BENCH_ROWS / BENCH_COLS / BENCH_ITERS env vars.
"""

import json
import os
import time

import numpy as np

BASELINE_ROW_ITERS_PER_SEC = 10_500_000 * 500 / 130.094


def run(metric: str = "binary_train_throughput",
        default_bin: int = 63) -> None:
    rows = int(os.environ.get("BENCH_ROWS", "4000000"))
    cols = int(os.environ.get("BENCH_COLS", "28"))
    iters = int(os.environ.get("BENCH_ITERS", "32"))
    num_leaves = int(os.environ.get("BENCH_LEAVES", "255"))
    max_bin = int(os.environ.get("BENCH_BIN", str(default_bin)))
    # BENCH_PROFILE=1: per-stage device timings ride along in the output
    # (runtime/profiler.py). NOTE: profiling fences every iteration, so
    # the throughput number is the per-iteration path, not the batched
    # scan — don't compare it against unprofiled runs.
    profile = os.environ.get("BENCH_PROFILE", "") not in ("", "0")
    # BENCH_AUTOTUNE=1: pick the grower by live probes (runtime/autotune.py)
    autotune = os.environ.get("BENCH_AUTOTUNE", "") not in ("", "0")

    import lightgbm_tpu as lgb
    from lightgbm_tpu.runtime.device import require_tpu
    device = require_tpu()

    rng = np.random.RandomState(42)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    w = rng.normal(size=cols)
    y = (X @ w + rng.normal(scale=0.5, size=rows) > 0).astype(np.float32)

    params = dict(objective="binary", num_leaves=num_leaves, max_bin=max_bin,
                  learning_rate=0.1, min_data_in_leaf=20, verbose=-1,
                  bagging_freq=0, device_profile=profile, autotune=autotune)
    ds = lgb.Dataset(X, label=y)

    # Training dispatches asynchronously: block on the scores before and
    # after the timed window.
    import jax

    def barrier(b):
        jax.block_until_ready(b._gbdt.scores)

    booster = lgb.Booster(params=params, train_set=ds)
    # two warmup chunks: the first pays jit compilation, the second any
    # one-time first-execution cost — the timed window then measures the
    # steady-state throughput a long training run sees.
    booster.update_batch(iters)
    barrier(booster)
    booster.update_batch(iters)
    barrier(booster)

    t0 = time.perf_counter()
    booster.update_batch(iters)
    barrier(booster)
    dt = time.perf_counter() - t0

    # train AUC over the 3x iters trained so far: guards against "fast but
    # wrong" — a kernel change that hurt split quality would show up here.
    # Uses the framework's own tie-aware AUCMetric so the gate and the
    # trainer's metric can never diverge.
    from lightgbm_tpu.metrics import create_metric

    sub = slice(0, min(rows, 500_000))
    pred = np.asarray(booster._gbdt.scores[0][:rows][sub])
    lab = y[sub]

    class _MD:
        label = lab
        weight = None
        query_boundaries = None

    m = create_metric("auc", booster._gbdt.config)
    m.init(_MD(), lab.size)
    auc = m.eval(pred, None)[0][1]

    row_iters_per_sec = rows * iters / dt
    out = {
        "metric": metric,
        "value": round(row_iters_per_sec, 1),
        "unit": "row_iters_per_sec",
        "vs_baseline": round(row_iters_per_sec / BASELINE_ROW_ITERS_PER_SEC,
                             4),
        "train_auc": round(float(auc), 5),
        "device": device,
    }
    if profile:
        p = booster.get_profile() or {}
        p.pop("ring", None)          # keep the line one line
        out["profile"] = p
    if autotune:
        out["autotune"] = booster._gbdt.autotune_decision
    print(json.dumps(out))


def main() -> None:
    # BENCH_SERVING=1: run the serving bench instead (naive per-call
    # predict vs micro-batched serving; scripts/bench_serving.py)
    # BENCH_ROWWISE=1: col-wise vs row-wise histogram layout bench
    # (scripts/bench_rowwise.py, docs/PERF.md section 3)
    # BENCH_COMM=1: histogram-exchange collective bench, allreduce vs
    # reduce_scatter vs packed (scripts/bench_comm.py, docs/PERF.md
    # section 5); writes BENCH_COMM.json
    # BENCH_RESIL=1: checkpointing overhead vs a plain update loop
    # (scripts/bench_resilience.py, docs/ROBUSTNESS.md); writes
    # BENCH_RESIL.json
    # BENCH_SLO=1: closed-loop overload bench, admission on vs off at
    # ~5x capacity with a fault-injected slow scorer
    # (scripts/bench_slo.py, docs/SERVING.md §Overload & SLOs); writes
    # BENCH_SLO.json
    # BENCH_ONLINE=1: online-loop bench, refresh latency + serving p99
    # during hot-swap refreshes vs idle + refit-vs-continue cost ratio
    # (scripts/bench_online.py, docs/ONLINE.md); writes
    # BENCH_ONLINE.json
    # BENCH_FLEET=1: multi-tenant fleet trace replay — zipfian tenant
    # popularity, diurnal load, a flash crowd on one tenant, hot-swaps
    # under traffic; pass/fail is per-tenant SLO isolation
    # (scripts/bench_fleet.py, docs/SERVING.md §Multi-tenant fleet);
    # writes BENCH_FLEET.json
    # BENCH_BATCHED=1: host-free training chunks vs the per-iteration
    # loop — wall speedup, dispatches/iteration, md5 parity + early-stop
    # truncation cross-checks (scripts/bench_batched.py, docs/PERF.md
    # §7); writes BENCH_BATCHED.json
    for env, script in (("BENCH_SERVING", "bench_serving.py"),
                        ("BENCH_ROWWISE", "bench_rowwise.py"),
                        ("BENCH_COMM", "bench_comm.py"),
                        ("BENCH_RESIL", "bench_resilience.py"),
                        ("BENCH_SLO", "bench_slo.py"),
                        ("BENCH_ONLINE", "bench_online.py"),
                        ("BENCH_FLEET", "bench_fleet.py"),
                        ("BENCH_BATCHED", "bench_batched.py")):
        if os.environ.get(env, "") not in ("", "0"):
            import runpy
            runpy.run_path(
                os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "scripts", script),
                run_name="__main__")
            return
    run()


if __name__ == "__main__":
    main()
