"""Kind ``train_rank``: one LambdaRank training job with a held-out set
scored and its NDCG taken after every tree, inside the scan.

Set-up makes the training and the held-out table from the seed
(bench/datagen_rank.py), builds ``lgb.Dataset(group=...)`` for each (the
held-out one with ``reference=``), one ``lgb.Booster`` + ``add_valid``,
and drives the booster through one chunk,
``Booster.update_batch(chunk_iters, chunk=chunk_iters)``: the same
object and the same call the window then repeats until ``--seconds``
have passed. A chunk ends on ``block_until_ready`` of the scores and on
the chunk's metric values on the host.

After the window the first chunk's trees, training and held-out scores,
NDCG values and the program's lambdas are held against the plain
reference (bench/reference/rank_ref.py) at the cell's full size
(bench/compare_rank.py).

A program that cannot keep this job inside the batched scan (no device
NDCG: every parent of the PR that brought this kind) is refused at
once, before any data is made.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict

import numpy as np

from kinds.common import Compiles, find_device, log, peak_bytes


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    cell, config = ctx["cell"], ctx["config"]
    job, data_spec = cell["job"], dict(cell["data"])
    seed, seconds, trace_on = ctx["seed"], ctx["seconds"], ctx["trace"]
    tamper = ctx.get("tamper")
    t0 = ctx["t0"]

    import jax
    import jax.numpy as jnp
    import compare
    import compare_rank
    import datagen_rank
    import trace_scopes
    from readers import program_span
    from reference import rank_ref

    import lightgbm_tpu as lgb
    from lightgbm_tpu import metrics as lgb_metrics
    if lgb_metrics.NDCGMetric.device_eval_fn \
            is lgb_metrics.Metric.device_eval_fn:
        raise SystemExit("train_rank: this program has no device NDCG, so "
                         "the job would leave the batched scan")
    device = find_device(ctx)
    compiles = Compiles()
    facts: Dict[str, Any] = {}
    chunk = int(job["chunk_iters"])
    data_spec.setdefault("cols", int(config["published"]["features"]))
    held_spec = dict(data_spec, **cell["heldout"])
    rows, held_rows = int(data_spec["rows"]), int(held_spec["rows"])
    params = dict(config["params"], verbose=-1)
    stated = dict(params)         # what the reference is held to
    if tamper is not None and hasattr(tamper, "params"):
        params = tamper.params(params)
    threads = int(job.get("threads", 8))

    t = time.perf_counter()
    X, y, lengths = datagen_rank.make(seed, data_spec, 0, threads)
    Xv, yv, held_lengths = datagen_rank.make(seed, held_spec, 1, threads)
    facts["datagen_s"] = time.perf_counter() - t
    log(f"data: {rows} + {held_rows} x {X.shape[1]} float32 in "
        f"{len(lengths)} + {len(held_lengths)} queries, "
        f"{facts['datagen_s']:.2f} s")
    held_group = held_lengths
    if tamper is not None and hasattr(tamper, "heldout_group"):
        held_group = tamper.heldout_group(held_lengths)

    # ---- ingest: raw rows -> binned, device-resident booster + valid set
    t = time.perf_counter()
    ds = lgb.Dataset(X, label=y, group=lengths, params=params)
    ds.construct()
    dv = lgb.Dataset(Xv, label=yv, group=held_group, reference=ds,
                     params=params)
    dv.construct()
    facts["dataset_construct_s"] = time.perf_counter() - t
    t = time.perf_counter()
    booster = lgb.Booster(params=params, train_set=ds)
    booster.add_valid(dv, "heldout")
    g = booster._gbdt
    jax.block_until_ready((g.X_t, g.scores, g._valid_Xt[0]))
    facts["booster_init_s"] = time.perf_counter() - t
    log(f"ingest: construct {facts['dataset_construct_s']:.2f} s, booster "
        f"+ add_valid {facts['booster_init_s']:.2f} s; grower {g.grower}")
    if not g.can_batch_iters(chunk):
        raise SystemExit("train_rank: can_batch_iters is false: the job "
                         "would run one dispatch a tree")
    for r in program_span.records() or []:
        if r["name"] == "objective/init":
            facts["rank_rows"] = r["counts"].get("rows")
            facts["rank_padded_rows"] = r["counts"].get("padded_rows")
            log(f"objective/init: {r['counts']}")
        if r["name"] == "metric/init":
            log(f"metric/init: {r['counts']}")
    if tamper is not None and hasattr(tamper, "after_init"):
        tamper.after_init(booster)

    def one_chunk() -> np.ndarray:
        vals = booster.update_batch(chunk, chunk=chunk)
        jax.block_until_ready(g.scores)
        vals = np.asarray(vals)             # the chunk's NDCG, on the host
        if tamper is not None and hasattr(tamper, "metrics"):
            vals = tamper.metrics(vals)
        return vals

    # ---- warm-up: the window's own call, once; its outputs are checked
    t = time.perf_counter()
    first_ndcg = one_chunk()
    facts["warmup_s"] = time.perf_counter() - t
    first_scores = np.asarray(g.scores[0, :rows])
    first_held = np.asarray(g._valid_scores[0][0, :held_rows])
    layout = booster.batched_eval_layout()
    facts["setup_compiles"] = compiles.n
    facts["setup_compile_s"] = compiles.seconds
    facts["setup_s"] = time.perf_counter() - t0
    log(f"warm-up chunk {facts['warmup_s']:.2f} s; compiles in set-up "
        f"{compiles.n} ({compiles.seconds:.2f} s); setup_s "
        f"{facts['setup_s']:.2f}; columns {[c[1] for c in layout]}")
    log(f"NDCG after each tree of the first chunk: {first_ndcg.tolist()}")

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
        reduction = trace_scopes.traced("bench:traced_chunk", one_chunk, ctx)
        facts["traced_trees"] = chunk

    # ---- what the timed path produced
    dump = booster.dump_model()["tree_info"]
    first_trees = dump[:chunk]
    facts["last_chunk_trees"] = dump[-chunk:]
    if tamper is not None and hasattr(tamper, "trees"):
        first_trees = tamper.trees(first_trees)
    facts["rows"], facts["features"] = rows, X.shape[1]
    handle = ds._handle
    n_cols = X.shape[1]
    inf_only = np.array([np.inf])
    edges = [inf_only] * n_cols     # a column ingest dropped: one bin
    for inner, orig in enumerate(handle.real_feature_index):
        edges[orig] = np.asarray(handle.mappers[inner].bin_upper_bound,
                                 np.float64)
    inner_of = np.asarray(handle.used_feature_map, np.int32)

    # the program's lambdas, at score 0 and at the first chunk's scores
    obj = g.objective
    grad_at = jax.jit(lambda s, st: obj.get_gradients(s, None, None, st))
    prog_grads = []
    for s in (np.zeros(rows, np.float32), first_scores):
        gl, gh = grad_at(jnp.asarray(s), obj.device_state())
        prog_grads.append((np.asarray(gl[:rows]), np.asarray(gh[:rows])))

    # ---- the reference, once the peak is read; program state freed as
    # soon as its bins have been compared
    t = time.perf_counter()
    chk = dict(job.get("check", {}))
    sub, ups = int(chk.get("sub", 16384)), int(chk.get("upload_subs", 64))
    used = jnp.asarray(np.maximum(inner_of, 0))
    kept = jnp.asarray(inner_of >= 0)[:, None]

    def bins_of(X_t):
        return lambda lo, hi: jnp.where(kept, X_t[used, lo:hi], 0)

    fol = rank_ref.RankFollower(edges, stated, sub=sub, upload_subs=ups)
    mismatch = fol.load_rows(X, y, program_bins=bins_of(g.X_t))
    held = rank_ref.RankFollower(edges, stated, sub=sub, upload_subs=ups)
    mismatch += held.load_rows(Xv, yv, program_bins=bins_of(g._valid_Xt[0]))
    del g, booster, ds, dv, obj, grad_at, handle
    gc.collect()
    trunc = int(stated.get("lambdarank_truncation_level", 30))
    eval_at = [int(k) for k in stated["eval_at"]]
    rk = rank_ref.Ranking(lengths, y, trunc, eval_at)
    rkv = rank_ref.Ranking(held_lengths, yv, trunc, eval_at)
    hist_trees = int(chk.get("hist_trees", 2))
    operand = str(config.get("histogram_operand_dtype", "float32"))
    ref = fol.follow_rank(first_trees, hist_trees, rk, held, rkv, operand)
    score_sets = (np.zeros(rows, np.float32), first_scores)
    ref_grads = [rk.lambdas(jnp.asarray(s), stated) for s in score_sets]
    pairs = [(p, np.asarray(r)) for pg, rg in zip(prog_grads, ref_grads)
             for p, r in zip(pg, rg)]
    view = compare_rank.tree_view(
        ref, first_scores, mismatch, compare_rank.edge_numbers(
            edges, fol.bin_count, rows, int(stated["max_bin"]),
            datagen_rank.continuous_columns(data_spec)))
    nums = compare_rank.numbers(ref, view, rows, first_held, pairs,
                                first_ndcg)
    verdict = compare.judge(nums, cell.get("limits", {}))
    facts["reference_s"] = time.perf_counter() - t
    facts["numbers"] = nums
    facts["reference_ndcg"] = ref["ndcg"].tolist()
    log(f"rows whose score is off the reference's: {view.get('rows_off')}")
    log(f"reference NDCG after each tree: {ref['ndcg'].tolist()}")
    control = ctx.get("control_dtype")
    if control:
        low = fol.follow_rank(first_trees, hist_trees, rk, held, rkv,
                              control, round_pairs_to="bfloat16")
        low_grads = [rk.lambdas(jnp.asarray(s), stated,
                                round_pairs_to="bfloat16")
                     for s in score_sets]
        low_pairs = [(np.asarray(a), np.asarray(b))
                     for lg, rg in zip(low_grads, ref_grads)
                     for a, b in zip(lg, rg)]
        facts["control_numbers"] = compare_rank.numbers(
            ref, compare_rank.control_view(low), rows,
            np.asarray(low["held_score"]), low_pairs, low["ndcg"])
    log(f"reference {facts['reference_s']:.2f} s over {len(first_trees)} "
        f"trees ({hist_trees} with histograms)")
    return {"facts": facts, "trace": reduction, "device": device,
            "verdict": verdict, "attempted": len(chunk_s) + 1, "failed": 0}
