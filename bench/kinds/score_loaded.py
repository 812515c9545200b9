"""Kind ``score_loaded``: batch scoring of a table of raw float32 rows,
most of whose cells are NaN, against a forest loaded from a model file.

A scoring job owns no trainer: it reads a model file and scores a table.
Set-up makes the table from the seed (bench/datagen_missing.py), makes
the forest from the seed in the shape the configuration states
(bench/forestgen.py), writes it as LightGBM model text and loads that
through the public entry, ``lgb.Booster(model_str=...)``; then scores the
whole table once with ``Booster.predict(X, raw_score=True)``. The window
repeats that call on the same table until ``--seconds`` have passed; a
pass ends when its margins are back on the host.

After the window every margin of the last pass is held against the
reference's walk (bench/reference/forest_ref_missing.py) of the arrays
the generator made, never of the program's parse of the text, over the
same rows, and the passes against each other. With a control asked for,
the reference is also read in the precision below (``feature_terms=2``)
and with the planted fault (a NaN read as 0.0 at every node), both
against the reference itself.
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
    import compare
    import datagen_missing
    import forestgen
    from reference import forest_ref_missing

    import lightgbm_tpu as lgb
    device = find_device(ctx)
    compiles = Compiles()
    facts: Dict[str, Any] = {}
    rows = int(data_spec["rows"])
    cols = int(data_spec.setdefault("cols", config["published"]["features"]))

    t = time.perf_counter()
    X = datagen_missing.make(seed, data_spec,
                             threads=int(job.get("threads", 8)))
    facts["datagen_s"] = time.perf_counter() - t
    facts["nan_share"] = sum(
        int(np.isnan(X[lo:lo + 65536]).sum())
        for lo in range(0, rows, 65536)) / X.size
    log(f"data: {rows} x {cols} float32 in {facts['datagen_s']:.2f} s; "
        f"nan_share {facts['nan_share']:.4f}")

    # ---- the forest under service: made, written, loaded
    t = time.perf_counter()
    spec = dict(config["forest"], cols=cols, rows=rows)
    spec.setdefault("trees", int(config["published"]["num_iterations"]))
    forest = forestgen.make(seed, spec)
    text = forestgen.model_text(forest, cols)
    facts["forest_gen_s"] = time.perf_counter() - t
    facts["model_text_bytes"] = len(text)
    facts.update(forest_ref_missing.forest_facts(forest))
    t = time.perf_counter()
    booster = lgb.Booster(model_str=text)
    facts["forest_load_s"] = time.perf_counter() - t
    del text
    log("forest: %d trees made in %.2f s, loaded from %d bytes of model "
        "text in %.2f s; forest_max_leaves %d, forest_nan_nodes %d" % (
            facts["forest_trees"], facts["forest_gen_s"],
            facts["model_text_bytes"], facts["forest_load_s"],
            facts["forest_max_leaves"], facts["forest_nan_nodes"]))

    # a planted fault may hand the program another table than the
    # reference reads (bench/tests: NaN as zero)
    Xp = tamper.table(X) if hasattr(tamper, "table") else X

    def one_pass() -> np.ndarray:
        m = booster.predict(Xp, raw_score=True)
        if hasattr(tamper, "margins"):
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
    facts["rows"], facts["features"] = rows, cols

    # ---- the reference, once the peak is read and the program is freed
    t = time.perf_counter()
    del booster, Xp
    gc.collect()
    block = int(job.get("check", {}).get("block", 1 << 17))
    ref = forest_ref_missing.score(X, forest, block=block)
    got = np.asarray(last).reshape(-1) if last is not None else None
    nums = compare.score_numbers(ref, got, differ)
    verdict = compare.judge(nums, cell.get("limits", {}))
    facts["reference_s"] = time.perf_counter() - t
    facts["numbers"] = nums
    if ctx.get("control_dtype"):
        low = forest_ref_missing.score(
            X, forest, block=block, feature_terms=int(ctx["control_dtype"]))
        zero = forest_ref_missing.score(X, forest, block=block,
                                        nan_as_zero=True)
        facts["control_numbers"] = dict(
            compare.score_numbers(ref, low, 0),
            nan_as_zero=compare.score_numbers(ref, zero, 0))
    log(f"reference {facts['reference_s']:.2f} s over "
        f"{facts['forest_trees']} trees, {rows} rows")
    return {"facts": facts, "trace": reduction, "device": device,
            "verdict": verdict, "attempted": len(pass_s) + 1, "failed": 0}
