"""Kind ``train_cat``: one binary training job on a table with categorical
columns, with a held-out set scored and its AUC taken after every tree,
inside the scan.

Set-up makes the training and the held-out table from the seed
(bench/datagen_cat.py), builds ``lgb.Dataset(categorical_feature=...)``
for each (the held-out one with ``reference=``), one ``lgb.Booster`` +
``add_valid``, and drives the booster through one chunk,
``Booster.update_batch(chunk_iters, chunk=chunk_iters)``: the same object
and the same call the window then repeats until ``--seconds`` have
passed. A chunk ends on ``block_until_ready`` of the scores and on the
chunk's AUC values on the host.

After the window the first chunk's trees, training and held-out scores
and AUC values are held against the plain reference
(bench/reference/gbdt_cat_ref.py) at the cell's full size
(bench/compare_cat.py).

A program that would not keep categorical columns on the kernels' path
is refused at once, before any data is made: one whose wave grower
routes a small categorical table to the ``[128, N]`` decision matrix, or
whose held-out path walk refuses categorical nodes (every parent of the
PR that brought this kind). A column whose map would pass 256 bins, a
table binned on the host, a held-out table the path walk cannot take, a
wave route other than the kernels' on a chip, or a traced chunk whose
device ran the decision matrix's ``lgbm_wave_apply`` fails the run
loudly.
"""

from __future__ import annotations

import gc
import time
from types import SimpleNamespace
from typing import Any, Dict

import numpy as np

from kinds.common import Compiles, find_device, log, peak_bytes


def refuse_unless_kernel_path() -> None:
    """SystemExit where a categorical table would leave the kernels."""
    from lightgbm_tpu.ops import grow_wave, predict
    x = np.zeros((8, 256), np.uint8)
    cfg = SimpleNamespace(num_bins_padded=256, bundled=False,
                          has_categorical=True, feature_parallel=False,
                          hist_impl="auto")
    if grow_wave.wave_route(x, cfg) == "matrix":
        raise SystemExit("train_cat: this program routes a categorical "
                         "table to the [128, N] decision matrix")
    if not predict.path_walk_applies(x, np.zeros((4,), bool)):
        raise SystemExit("train_cat: this program's held-out walk refuses "
                         "categorical nodes (the gather walk)")


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    cell, config = ctx["cell"], ctx["config"]
    job, data_spec = cell["job"], dict(cell["data"])
    seed, seconds, trace_on = ctx["seed"], ctx["seconds"], ctx["trace"]
    tamper = ctx.get("tamper")
    t0 = ctx["t0"]

    refuse_unless_kernel_path()
    import jax
    import jax.numpy as jnp
    import compare
    import compare_cat
    import datagen_cat
    import trace_scopes
    from readers import program_span
    from reference import gbdt_cat_ref

    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops import predict
    device = find_device(ctx)
    compiles = Compiles()
    facts: Dict[str, Any] = {}
    chunk = int(job["chunk_iters"])
    held_spec = dict(data_spec, **cell["heldout"])
    rows, held_rows = int(data_spec["rows"]), int(held_spec["rows"])
    params = dict(config["params"], verbose=-1)
    stated = dict(params)         # what the reference is held to
    if tamper is not None and hasattr(tamper, "params"):
        params = tamper.params(params)
    cats = [int(c) for c in params["categorical_feature"]]
    threads = int(job.get("threads", 8))

    t = time.perf_counter()
    X, y = datagen_cat.make(seed, data_spec, 0, threads)
    Xv, yv = datagen_cat.make(seed, held_spec, 1, threads)
    facts["datagen_s"] = time.perf_counter() - t
    log(f"data: {rows} + {held_rows} x {X.shape[1]} float32, positives "
        f"{float(y.mean()):.4f} / {float(yv.mean()):.4f}, "
        f"{facts['datagen_s']:.2f} s")

    # ---- ingest: raw rows -> binned, device-resident booster + valid set
    t = time.perf_counter()
    ds = lgb.Dataset(X, label=y, categorical_feature=cats, params=params)
    ds.construct()
    dv = lgb.Dataset(Xv, label=yv, categorical_feature=cats, reference=ds,
                     params=params)
    dv.construct()
    facts["dataset_construct_s"] = time.perf_counter() - t
    handle = ds._handle
    nbins = [int(m.num_bin) for m in handle.mappers]
    log(f"bins by column: {dict(zip(handle.real_feature_index, nbins))}; "
        f"binned on {handle.binned_on}")
    if max(nbins) > 256:
        raise SystemExit(f"train_cat: a column maps to {max(nbins)} bins, "
                         "over the 256 the device binning and the uint8 "
                         "storage hold")
    if ctx["require_chip"] and handle.binned_on != "device":
        raise SystemExit("train_cat: the table was binned on the host")
    t = time.perf_counter()
    booster = lgb.Booster(params=params, train_set=ds)
    booster.add_valid(dv, "heldout")
    g = booster._gbdt
    jax.block_until_ready((g.X_t, g.scores, g._valid_Xt[0]))
    facts["booster_init_s"] = time.perf_counter() - t
    route = g._wave_route()
    log(f"ingest: construct {facts['dataset_construct_s']:.2f} s, booster "
        f"+ add_valid {facts['booster_init_s']:.2f} s; grower {g.grower}, "
        f"wave route {route}, B {g.num_bins_padded}")
    if ctx["require_chip"] and route not in ("mega", "decide"):
        raise SystemExit(f"train_cat: wave route {route!r} on the chip")
    if not predict.path_walk_applies(g._valid_Xt[0],
                                     np.ones((4,), bool)):
        raise SystemExit("train_cat: the held-out table would take the "
                         "gather walk")
    if not g.can_batch_iters(chunk):
        raise SystemExit("train_cat: can_batch_iters is false: the job "
                         "would run one dispatch a tree")
    if tamper is not None and hasattr(tamper, "after_init"):
        tamper.after_init(booster)

    def one_chunk() -> np.ndarray:
        before = g.scores
        vals = booster.update_batch(chunk, chunk=chunk)
        if tamper is not None and hasattr(tamper, "after_chunk"):
            tamper.after_chunk(booster, before)
        jax.block_until_ready(g.scores)
        vals = np.asarray(vals)             # the chunk's AUC, on the host
        if tamper is not None and hasattr(tamper, "metrics"):
            vals = tamper.metrics(vals)
        return vals

    # ---- warm-up: the window's own call, once; its outputs are checked
    t = time.perf_counter()
    first_auc = one_chunk()
    facts["warmup_s"] = time.perf_counter() - t
    first_scores = np.asarray(g.scores[0, :rows])
    first_held = np.asarray(g._valid_scores[0][0, :held_rows])
    facts["setup_compiles"] = compiles.n
    facts["setup_compile_s"] = compiles.seconds
    facts["setup_s"] = time.perf_counter() - t0
    log(f"warm-up chunk {facts['warmup_s']:.2f} s; compiles in set-up "
        f"{compiles.n} ({compiles.seconds:.2f} s); setup_s "
        f"{facts['setup_s']:.2f}")
    log(f"held-out AUC after each tree of the first chunk: "
        f"{first_auc.reshape(-1).tolist()}")

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
        # the route as the device ran it: the decision matrix's kernel
        # (not the in-kernel decision's, lgbm_wave_apply_f<F>) is absent
        if reduction is not None and reduction.matched_s(
                [r"lgbm_wave_apply(?!_f)"]):
            raise SystemExit("train_cat: the traced chunk ran the [128, N] "
                             "decision matrix's lgbm_wave_apply")

    # ---- what the timed path produced
    dump = booster.dump_model()["tree_info"]
    first_trees = dump[:chunk]
    facts["last_chunk_trees"] = dump[-chunk:]
    if tamper is not None and hasattr(tamper, "trees"):
        first_trees = tamper.trees(first_trees)
    chunks = [r for r in program_span.records() or []
              if r["name"] == "train/chunk" and r["parent"] is None]
    facts["cat_splits"] = sum(r["counts"].get("cat_splits", 0)
                              for r in chunks)
    facts["chunk_trees"] = sum(r["counts"].get("trees", 0) for r in chunks)
    log(f"categorical splits {facts['cat_splits']} of "
        f"{sum(t['num_leaves'] - 1 for t in dump)} in "
        f"{facts['chunk_trees']} trees; train/chunk wave_route "
        f"{sorted({r['counts'].get('wave_route') for r in chunks}, key=str)}")
    facts["rows"], facts["features"] = rows, X.shape[1]
    facts["feature_bins"] = nbins
    n_cols = X.shape[1]
    edges = [np.array([np.inf])] * n_cols    # a column ingest dropped
    cat_maps: Dict[int, Dict[int, int]] = {}
    for inner, orig in enumerate(handle.real_feature_index):
        m = handle.mappers[inner]
        edges[orig] = np.asarray(m.bin_upper_bound, np.float64)
        if orig in cats:
            cat_maps[orig] = {int(c): int(b)
                              for c, b in m.categorical_2_bin.items()
                              if c >= 0}
    inner_of = np.asarray(handle.used_feature_map, np.int32)

    # ---- the reference, once the peak is read; program state freed as
    # soon as its bins have been compared
    t = time.perf_counter()
    chk = dict(job.get("check", {}))
    sub, ups = int(chk.get("sub", 16384)), int(chk.get("upload_subs", 64))
    used = jnp.asarray(np.maximum(inner_of, 0))
    kept = jnp.asarray(inner_of >= 0)[:, None]

    def bins_of(X_t):
        return lambda lo, hi: jnp.where(kept, X_t[used, lo:hi], 0)

    fol = gbdt_cat_ref.CatFollower(edges, cat_maps, stated, sub=sub,
                                   upload_subs=ups)
    mismatch = fol.load_rows(X, y, program_bins=bins_of(g.X_t),
                             threads=threads)
    held = gbdt_cat_ref.CatFollower(edges, cat_maps, stated, sub=sub,
                                    upload_subs=ups)
    mismatch += held.load_rows(Xv, yv, program_bins=bins_of(g._valid_Xt[0]),
                               threads=threads)
    del g, booster, ds, dv, handle
    gc.collect()
    hist_trees = int(chk.get("hist_trees", 2))
    operand = str(config.get("histogram_operand_dtype", "float32"))
    ref = fol.follow(first_trees, hist_trees, operand, held, yv)
    view = compare_cat.tree_view(ref, first_scores, y, mismatch,
                                 fol.map_numbers(int(stated["max_bin"])))
    nums = compare_cat.numbers(fol, ref, view, rows, first_held, first_auc)
    verdict = compare.judge(nums, cell.get("limits", {}))
    facts["reference_s"] = time.perf_counter() - t
    facts["numbers"] = nums
    log(f"rows whose score is off the reference's: {view.get('rows_off')}")
    log(f"reference AUC after each tree: {ref['auc'].tolist()}")
    control = ctx.get("control_dtype")
    if control:
        low = fol.follow(first_trees, hist_trees, control, held, yv)
        lv = compare_cat.control_view(low, rows)
        facts["control_numbers"] = compare_cat.numbers(
            fol, ref, lv, rows, lv["held_score"], lv["auc"])
    log(f"reference {facts['reference_s']:.2f} s over {len(first_trees)} "
        f"trees ({hist_trees} with histograms)")
    return {"facts": facts, "trace": reduction, "device": device,
            "verdict": verdict, "attempted": len(chunk_s) + 1, "failed": 0}
