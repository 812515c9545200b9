"""Kind ``train``: one training job on one booster.

Set-up makes the data from the seed, builds ``lgb.Dataset`` and
``lgb.Booster`` from the raw float32 rows, and drives the booster through
one chunk (``Booster.update_batch(chunk_iters, chunk=chunk_iters)``): the
same object and the same call the window then repeats until ``--seconds``
have passed. Every chunk ends on ``block_until_ready`` of the scores.

After the window the device scores and trees of that first chunk are
held against the plain reference (bench/reference/gbdt_ref.py) at the
cell's full size: device binning, gradients, the splits of the first
``hist_trees`` trees, every leaf output and count, the score update and
the log-loss. The reference bins with ingest's own edges (the binning
sample is the program's draw), so the edges are held beside it to what
the configuration states (compare.edge_numbers): at most ``max_bin``
rising bounds a column, no bin much heavier than its even share.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict

import numpy as np

from kinds.common import Compiles, find_device, log, peak_bytes, traced


def auc(scores: np.ndarray, y: np.ndarray) -> float:
    """Tie-aware area under the ROC curve (mean rank of the positives)."""
    order = np.argsort(scores, kind="stable")
    s = scores[order]
    rank = np.empty(len(s), np.float64)
    # average rank within runs of equal scores
    starts = np.flatnonzero(np.concatenate([[True], s[1:] != s[:-1]]))
    ends = np.concatenate([starts[1:], [len(s)]])
    avg = (starts + ends + 1) / 2.0
    rank[order] = np.repeat(avg, ends - starts)
    pos = y > 0
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    return float((rank[pos].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    cell, config = ctx["cell"], ctx["config"]
    job, data_spec = cell["job"], dict(cell["data"])
    seed, seconds, trace_on = ctx["seed"], ctx["seconds"], ctx["trace"]
    tamper = ctx.get("tamper")
    t0 = ctx["t0"]

    import jax
    import datagen
    import compare
    from reference import gbdt_ref

    import lightgbm_tpu as lgb
    device = find_device(ctx)
    compiles = Compiles()
    facts: Dict[str, Any] = {}
    rows, chunk = int(data_spec["rows"]), int(job["chunk_iters"])
    data_spec.setdefault("cols", int(config["published"]["features"]))
    params = dict(config["params"], verbose=-1)
    stated = dict(params)         # what the reference and the edges are held to
    if tamper is not None and hasattr(tamper, "params"):
        params = tamper.params(params)

    t = time.perf_counter()
    X, y = datagen.make(seed, data_spec, threads=int(job.get("threads", 8)))
    facts["datagen_s"] = time.perf_counter() - t
    log(f"data: {rows} x {X.shape[1]} float32 in {facts['datagen_s']:.2f} s")

    # ---- ingest: raw rows -> binned, device-resident booster
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench:dataset_construct"):
        ds = lgb.Dataset(X, label=y, params=params)
        ds.construct()
    facts["dataset_construct_s"] = time.perf_counter() - t
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench:booster_init"):
        booster = lgb.Booster(params=params, train_set=ds)
        g = booster._gbdt
        jax.block_until_ready((g.X_t, g.scores))
    facts["booster_init_s"] = time.perf_counter() - t
    facts["ingest_s"] = facts["dataset_construct_s"] + facts["booster_init_s"]
    facts["ingest_rows_per_s"] = rows / facts["ingest_s"]
    log(f"ingest: construct {facts['dataset_construct_s']:.2f} s, booster "
        f"{facts['booster_init_s']:.2f} s, binned on "
        f"{getattr(ds._handle, 'binned_on', '?')}")
    # which phase of ingest took the time (some runs read 95 s for 57)
    from readers import program_span
    for root, kids in program_span.trees(program_span.records() or [],
                                         "dataset/construct"):
        log("ingest spans: " + ", ".join(
            f"{k['name'].split('/')[-1]} "
            f"{(k['end_ns'] - k['start_ns']) / 1e9:.2f} s"
            for k in kids if k["parent"] == root["id"]))
    if tamper is not None and hasattr(tamper, "after_init"):
        tamper.after_init(booster)

    def one_chunk() -> None:
        before = g.scores
        booster.update_batch(chunk, chunk=chunk)
        if tamper is not None and hasattr(tamper, "after_chunk"):
            tamper.after_chunk(booster, before)
        jax.block_until_ready(g.scores)

    # ---- warm-up: the window's own call, once; its outputs are checked
    t = time.perf_counter()
    one_chunk()
    facts["warmup_s"] = time.perf_counter() - t
    first_scores = np.asarray(g.scores[0, :rows])
    n_auc = min(rows, int(job.get("auc_rows", 500000)))
    facts["train_auc"] = auc(first_scores[:n_auc], y[:n_auc])
    facts["setup_compiles"] = compiles.n
    facts["setup_compile_s"] = compiles.seconds
    facts["setup_s"] = time.perf_counter() - t0
    log(f"warm-up chunk {facts['warmup_s']:.2f} s; compiles in set-up "
        f"{compiles.n} ({compiles.seconds:.2f} s); setup_s "
        f"{facts['setup_s']:.2f}")

    # ---- the window
    d0, c0 = g.dispatch_count, compiles.n
    chunk_s = []
    w0 = time.perf_counter()
    while True:
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:update_batch"):
            one_chunk()
        now = time.perf_counter()
        chunk_s.append(now - t)
        if now - w0 >= seconds:
            break
    window_s = time.perf_counter() - w0
    trees_done = chunk * len(chunk_s)
    facts.update(
        window_s=window_s, chunks_in_window=len(chunk_s),
        trees_in_window=trees_done, chunk_s=chunk_s,
        train_row_iters_per_s=rows * trees_done / window_s,
        dispatches_in_window=g.dispatch_count - d0,
        compiles_in_window=compiles.n - c0)
    log("window: %d chunks of %d trees in %.3f s; chunk seconds %s; "
        "compiles in window %d" % (len(chunk_s), chunk, window_s,
                                   [round(c, 3) for c in chunk_s],
                                   facts["compiles_in_window"]))
    facts["memory_peak_bytes"] = peak_bytes()

    # ---- one traced chunk
    reduction = None
    if trace_on:
        reduction = traced("bench:traced_chunk", one_chunk, ctx)
        facts["traced_trees"] = chunk

    # ---- what the timed path produced
    dump = booster.dump_model()["tree_info"]
    first_trees = dump[:chunk]
    facts["last_chunk_trees"] = dump[-chunk:]
    if tamper is not None and hasattr(tamper, "trees"):
        first_trees = tamper.trees(first_trees)
    edges = [np.asarray(m.bin_upper_bound, np.float64)
             for m in ds._handle.mappers]
    facts["rows"], facts["features"] = rows, X.shape[1]

    # ---- the reference, once the peak is read; program state freed
    # as soon as its bins have been compared
    t = time.perf_counter()
    chk = dict(job.get("check", {}))
    fol = gbdt_ref.Follower(edges, stated, sub=int(chk.get("sub", 16384)),
                            upload_subs=int(chk.get("upload_subs", 64)))
    X_t = g.X_t
    mismatch = fol.load_rows(X, y, program_bins=lambda lo, hi: X_t[:, lo:hi])
    del X_t, g, booster, ds
    gc.collect()
    hist_trees = int(chk.get("hist_trees", 2))
    ref = fol.follow(first_trees, hist_trees,
                     str(config.get("histogram_operand_dtype", "float32")))
    view = compare.program_view(
        ref, first_scores, y, mismatch, compare.edge_numbers(
            edges, fol.bin_count, rows, int(stated["max_bin"])))
    nums = compare.numbers(ref, view, rows)
    verdict = compare.judge(nums, cell.get("limits", {}))
    facts["reference_s"] = time.perf_counter() - t
    facts["numbers"] = nums
    log(f"rows whose score is off the reference's: {view.get('rows_off')}")
    if ctx.get("detail"):
        facts["detail"] = [{
            "prog_gain": r["tables"].prog_gain.tolist(),
            "gain_chosen": r.get("gain_chosen", np.zeros(0)).tolist(),
            "gain_best": r.get("gain_best", np.zeros(0)).tolist(),
            "node_count": r["node_count"].tolist(),
            "prog_node_count": r["tables"].prog_icount.tolist(),
            "leaf_count": r["leaf_count"].tolist(),
            "prog_leaf_count": r["tables"].prog_lcount.tolist(),
            "leaf_step": r["leaf_step"].tolist(),
            "prog_leaf_step": (r["tables"].prog_lval - r["bias"]).tolist(),
            "leaf_H": r["leaf_H"].tolist(), "leaf_G": r["leaf_G"].tolist(),
            "depth": r["tables"].depth, "loss_before": r["loss_before"],
            "leaf_depth": r["tables"].plen[:r["tables"].n_leaf, 0].tolist(),
        } for r in ref["trees"]]
    control = ctx.get("control_dtype")
    if control:
        low = fol.follow(first_trees, hist_trees, control)
        facts["control_numbers"] = compare.numbers(
            ref, compare.control_view(low, rows), rows)
    log(f"reference {facts['reference_s']:.2f} s over {len(first_trees)} "
        f"trees ({hist_trees} with histograms)")
    return {"facts": facts, "trace": reduction, "device": device,
            "verdict": verdict, "attempted": len(chunk_s) + 1, "failed": 0}
