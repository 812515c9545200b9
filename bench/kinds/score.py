"""Kind ``score``: batch scoring of a table of raw float32 rows.

Set-up makes the table from the seed, trains the configuration's forest
on its first ``train_rows`` rows (the program's own trainer: the forest
is the model under service, not what this cell times), and scores the
whole table once through the public entry, ``Booster.predict(X,
raw_score=True)``: float32 batches of 100,000 rows or more go to the
device predictor (models/predictor.py predict_margin_device). The window
repeats that same call on the same table until ``--seconds`` have
passed; a pass ends when its margins are back on the host.

After the window every margin of the last pass is held against the plain
reference's walk of the drained trees over the same rows
(bench/reference/forest_ref.py), and the passes against each other.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict

import numpy as np

from kinds.common import Compiles, find_device, log, peak_bytes, traced


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    cell, config = ctx["cell"], ctx["config"]
    job, data_spec = cell["job"], dict(cell["data"])
    seed, seconds, trace_on = ctx["seed"], ctx["seconds"], ctx["trace"]
    tamper = ctx.get("tamper")
    t0 = ctx["t0"]

    import jax
    import datagen
    import compare
    from reference import forest_ref

    import lightgbm_tpu as lgb
    device = find_device(ctx)
    compiles = Compiles()
    facts: Dict[str, Any] = {}
    rows = int(data_spec["rows"])
    data_spec.setdefault("cols", int(config["published"]["features"]))
    params = dict(config["params"], verbose=-1)
    n_trees = int(params.pop("num_iterations"))
    train_rows = min(rows, int(job["train_rows"]))

    t = time.perf_counter()
    X, y = datagen.make(seed, data_spec, threads=int(job.get("threads", 8)))
    facts["datagen_s"] = time.perf_counter() - t
    log(f"data: {rows} x {X.shape[1]} float32 in {facts['datagen_s']:.2f} s")

    # ---- the forest under service
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench:forest_train"):
        ds = lgb.Dataset(X[:train_rows], label=y[:train_rows],
                         params=params)
        booster = lgb.Booster(params=params, train_set=ds)
        booster.update_batch(n_trees, chunk=int(job["train_chunk"]))
        jax.block_until_ready(booster._gbdt.scores)
        trees = booster.dump_model()["tree_info"]
    facts["forest_train_s"] = time.perf_counter() - t
    facts["forest_trees"] = len(trees)
    log(f"forest: {len(trees)} trees on {train_rows} rows in "
        f"{facts['forest_train_s']:.2f} s")

    def one_pass() -> np.ndarray:
        m = booster.predict(X, raw_score=True)
        if tamper is not None and hasattr(tamper, "margins"):
            m = tamper.margins(m)
        return m

    t = time.perf_counter()
    last = one_pass()
    facts["warmup_s"] = time.perf_counter() - t
    facts["setup_compiles"] = compiles.n
    facts["setup_s"] = time.perf_counter() - t0
    log(f"warm-up pass {facts['warmup_s']:.2f} s; compiles in set-up "
        f"{compiles.n} ({compiles.seconds:.2f} s); setup_s "
        f"{facts['setup_s']:.2f}")

    # ---- the window
    c0 = compiles.n
    pass_s, differ = [], 0
    w0 = time.perf_counter()
    while True:
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:predict"):
            m = one_pass()
        now = time.perf_counter()
        pass_s.append(now - t)
        differ += int(not np.array_equal(m, last))
        last = m
        if now - w0 >= seconds:
            break
    window_s = time.perf_counter() - w0
    facts.update(window_s=window_s, passes_in_window=len(pass_s),
                 pass_s=pass_s,
                 score_rows_per_s=rows * len(pass_s) / window_s,
                 compiles_in_window=compiles.n - c0)
    log("window: %d passes in %.3f s; pass seconds %s; compiles in "
        "window %d" % (len(pass_s), window_s,
                       [round(p, 3) for p in pass_s],
                       facts["compiles_in_window"]))
    facts["memory_peak_bytes"] = peak_bytes()

    reduction = None
    if trace_on:
        reduction = traced("bench:traced_pass", one_pass, ctx)
        facts["traced_passes"] = 1

    facts["rows"], facts["features"] = rows, X.shape[1]
    facts["forest_mean_depth"] = forest_ref.mean_depth(trees)

    # ---- the reference, once the peak is read and the program is freed
    t = time.perf_counter()
    del booster, ds
    gc.collect()
    chk = dict(job.get("check", {}))
    block = int(chk.get("block", 1 << 20))
    ref = forest_ref.score(X, trees, block=block)
    got = np.asarray(last).reshape(-1) if last is not None else None
    nums = compare.score_numbers(ref, got, differ)
    verdict = compare.judge(nums, cell.get("limits", {}))
    facts["reference_s"] = time.perf_counter() - t
    facts["numbers"] = nums
    if ctx.get("control_dtype"):
        low = forest_ref.score(X, trees, block=block,
                               feature_terms=int(ctx["control_dtype"]))
        facts["control_numbers"] = compare.score_numbers(ref, low, 0)
    log(f"reference {facts['reference_s']:.2f} s over {len(trees)} trees, "
        f"{rows} rows")
    return {"facts": facts, "trace": reduction, "device": device,
            "verdict": verdict, "attempted": len(pass_s) + 1, "failed": 0}
