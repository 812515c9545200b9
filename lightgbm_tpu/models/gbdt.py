"""GBDT training orchestrator.

TPU-native analog of src/boosting/gbdt.cpp (GBDT::Init:60, TrainOneIter:353,
Train:246, UpdateScore:502) + model (de)serialization
(gbdt_model_text.cpp:321 SaveModelToString, LoadModelFromString).

Device/host split: scores, gradients, the binned matrix and tree growth live
on device; grown trees stay on device as `DeviceTree` records and are only
materialized into host `Tree` objects (for model export / raw-data
prediction) lazily and in batches — the training loop itself issues NO host
synchronization, so iterations stream asynchronously to the device. This
goes further than the CUDA design (SURVEY.md §3.5, one small readback per
split): here the readback is deferred past the whole training run unless a
caller needs host trees earlier (save/predict/DART/RF paths).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import os
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..data.binning import BIN_TYPE_CATEGORICAL
from ..data.dataset import BinnedDataset
from ..metrics import Metric
from ..objectives import ObjectiveFunction
from ..ops.grow import DeviceTree, GrowConfig, grow_tree
from ..ops.predict import predict_leaf_binned
from ..ops.split import FeatureMeta
from ..utils.log import log_fatal, log_info, log_warning
from ..runtime.device import start_backend
from ..runtime.profiler import count as span_count, span
from .tree import Tree, make_decision_type

_KEPS = 1e-15
MODEL_VERSION = "v4"


from ..utils import round_up as _round_up


def _parse_interaction_constraints(spec) -> List[List[int]]:
    """'[0,1,2],[2,3]' or a list of lists -> list of real-index groups
    (reference: config.h interaction_constraints)."""
    if not spec:
        return []
    if isinstance(spec, str):
        import re
        return [[int(x) for x in grp.split(",") if x.strip() != ""]
                for grp in re.findall(r"\[([^\]]*)\]", spec)]
    return [list(map(int, grp)) for grp in spec]


def parse_forced_splits(filename: str, ds: BinnedDataset) -> np.ndarray:
    """forcedsplits_filename JSON -> [4, S] i32 BFS table of
    (inner_feature, bin_threshold, left_id, right_id), -1 = no child
    (reference: the nested {feature, threshold, left, right} JSON read in
    SerialTreeLearner::Init and walked by ForceSplits,
    serial_tree_learner.cpp:628). Real thresholds convert to bin
    thresholds through the feature's bin mapper."""
    import json as _json
    from ..utils.log import log_fatal as _fatal, log_warning as _warn
    with open(filename) as f:
        root = _json.load(f)
    if not root:
        return None
    real2inner = {r: i for i, r in enumerate(ds.real_feature_index)}
    rows = []                # (feature, bin_thr, left, right)
    queue = [(root, -1, "")]
    while queue:
        node, parent_idx, side = queue.pop(0)
        real_f = int(node["feature"])
        thr = float(node["threshold"])
        if real_f not in real2inner:
            _warn(f"forced split on trivial/unused feature {real_f} "
                  "ignored (its branch stops forcing)")
            continue
        inner = real2inner[real_f]
        m = ds.mappers[inner]
        if bool(np.asarray(ds.feature_is_categorical())[inner]):
            _fatal("forced splits on categorical features are not "
                   "supported")
        bin_thr = int(m.value_to_bin(np.asarray([thr], np.float64))[0])
        idx = len(rows)
        rows.append([inner, bin_thr, -1, -1])
        if parent_idx >= 0:
            rows[parent_idx][2 if side == "left" else 3] = idx
        for s in ("left", "right"):
            if isinstance(node.get(s), dict) and node[s]:
                queue.append((node[s], idx, s))
    if not rows:
        return None
    return np.asarray(rows, np.int32).T          # [4, S]


def build_feature_meta(ds: BinnedDataset,
                       monotone: Optional[Sequence[int]] = None,
                       interactions=None) -> FeatureMeta:
    from ..utils.log import log_fatal as _fatal
    mono_arr = None
    if monotone:
        # config lists constraints by REAL feature index; map to the used
        # (inner) features. The reference Log::Fatals on a size mismatch
        # (config.cpp CheckParamConflict) — same here, no silent drops.
        if len(monotone) != ds.num_total_features:
            _fatal(f"monotone_constraints has {len(monotone)} entries but "
                   f"the dataset has {ds.num_total_features} features")
        mono = np.zeros(len(ds.mappers), np.int8)
        for inner, real in enumerate(ds.real_feature_index):
            mono[inner] = np.sign(monotone[real])
        if mono.any():
            mono_arr = jnp.asarray(mono)
    inter_arr = None
    groups = _parse_interaction_constraints(interactions)
    if groups:
        real2inner = {r: i for i, r in enumerate(ds.real_feature_index)}
        sets = np.zeros((len(groups), len(ds.mappers)), bool)
        for s, grp in enumerate(groups):
            for real in grp:
                if real >= ds.num_total_features or real < 0:
                    _fatal(f"interaction_constraints references feature "
                           f"{real}, but the dataset has "
                           f"{ds.num_total_features} features")
                if real in real2inner:   # unused (trivial) features are
                    sets[s, real2inner[real]] = True  # legitimately absent
        inter_arr = jnp.asarray(sets)
    return FeatureMeta(
        num_bins=jnp.asarray(ds.feature_num_bins()),
        missing_type=jnp.asarray(ds.feature_missing_types()),
        default_bin=jnp.asarray(ds.feature_default_bins()),
        is_categorical=jnp.asarray(ds.feature_is_categorical()),
        monotone=mono_arr,
        inter_sets=inter_arr,
    )


class GBDT:
    """Gradient Boosting Decision Trees (reference: src/boosting/gbdt.h:35)."""

    _pre_part = False            # set by _init_train when pre-partitioned
    _fault_plan = None           # resilience: runtime/faults.py plan or None
    _collective_failures = 0     # watchdog: histogram-exchange error count

    def __init__(self, config: Config, train_set: Optional[BinnedDataset],
                 objective: Optional[ObjectiveFunction],
                 training_metrics: Sequence[Metric] = ()):
        self.config = config
        self.objective = objective
        self.train_set = train_set
        self.training_metrics = list(training_metrics)
        self._models: List[Tree] = []
        # device-resident trees not yet materialized on host: list of
        # (DeviceTree, bias_to_fold). Drained in ONE device_get by
        # _materialize_models().
        self._pending: List[Tuple[Any, float]] = []
        # the open train/chunk span, and a chunk record's bias list (by
        # id) -> its span, which the drain gives the chunk's cat_splits
        self._chunk_span = None
        self._chunk_spans: Dict[int, Any] = {}
        # how often train_one_iter really checks the "no more splits"
        # condition; every check costs one host sync, so it is amortized
        self._stop_check_interval = 32
        self._stopped = False
        self.iter = 0
        self.num_class = config.num_class
        self.num_tree_per_iteration = (
            objective.num_model_per_iteration if objective else config.num_class)
        self.shrinkage_rate = config.learning_rate
        self.average_output = False   # RF mode overrides
        self.valid_sets: List[BinnedDataset] = []
        self.valid_names: List[str] = []
        self._valid_scores: List[jnp.ndarray] = []
        self._valid_meta: List[FeatureMeta] = []
        self._valid_Xt: List[jnp.ndarray] = []
        # batched training (docs/PERF.md §7): per-valid-set metric objects
        # + device label/weight for in-scan eval, the bounded scan-fn
        # cache, the async tree-drain worker, and the jitted-dispatch
        # counter (bench_batched.py's dispatches-per-iteration number)
        self._valid_metrics: List[List[Metric]] = []
        self._valid_label_dev: List[Optional[jnp.ndarray]] = []
        self._valid_weight_dev: List[jnp.ndarray] = []
        self._valid_sumw: List[float] = []
        self._drain = None
        self.dispatch_count = 0
        self.best_iteration = -1
        self.loaded_parameter = ""
        self.max_feature_idx_ = 0
        self.feature_names_: List[str] = []
        self.feature_infos_: List[str] = []
        self.label_idx_ = 0
        # runtime subsystem state (lightgbm_tpu/runtime/)
        self.profiler = None
        self.autotune_decision: Optional[Dict[str, Any]] = None

        if train_set is not None:
            with span("booster/init", rows=train_set.num_data):
                self._init_train(train_set)

    # ------------------------------------------------------------------
    def _init_train(self, ds: BinnedDataset) -> None:
        cfg = self.config
        if cfg.device_profile:
            from ..runtime import StageProfiler
            self.profiler = StageProfiler()
            self.profiler.straggler_threshold = float(
                cfg.straggler_skew_threshold)
        # deterministic fault injection (runtime/faults.py); None — the
        # default — costs one `is None` check per iteration
        from ..runtime.faults import active_plan
        self._fault_plan = active_plan(cfg.fault_plan)
        self.num_data = ds.num_data
        self.max_feature_idx_ = ds.num_total_features - 1
        self.feature_names_ = list(ds.feature_names)
        self.feature_infos_ = ds.feature_infos()
        self.mappers = ds.mappers
        self.real_feature_index = list(ds.real_feature_index)

        # -- device layout: serial (one device) vs data-parallel (rows
        #    sharded over the mesh `data` axis; reference tree_learner=data,
        #    SURVEY.md §3.4). feature/voting learners currently run on the
        #    data-parallel path too: with histograms psum-reduced the voting
        #    compression and per-rank feature ownership are pure comm
        #    optimizations, not semantic ones.
        from ..parallel import lane_multiple, make_data_mesh, pad_rows_to
        n_dev = jax.device_count()
        self.use_dist = (cfg.tree_learner in ("data", "feature", "voting")
                         and n_dev > 1)
        N_real = ds.num_data
        self._pre_part = (bool(cfg.pre_partition) and self.use_dist
                          and jax.process_count() > 1)
        # true feature-parallel (feature_parallel_tree_learner.cpp):
        # every shard holds ALL rows; features partition per tree
        self._feat_par = (self.use_dist and cfg.tree_learner == "feature")
        if self._feat_par and self._pre_part:
            log_fatal("tree_learner=feature requires the full dataset on "
                      "every machine (pre_partition=true contradicts it)")
        if self._feat_par:
            self.mesh = make_data_mesh()
            self.n_shards = int(self.mesh.devices.size)
            self.N_pad = N_real
            self._host_pad = N_real
            log_info(f"Feature-parallel training over {self.n_shards} "
                     f"devices (rows replicated, features partitioned)")
        elif self.use_dist:
            self.mesh = make_data_mesh()
            self.n_shards = int(self.mesh.devices.size)
            if self._pre_part:
                # pre-partitioned load (dataset_loader.cpp:1162-1213):
                # every process holds ONLY its own rows; the global row
                # space is the concatenation of the per-process shards
                from jax.experimental import multihost_utils
                nproc = jax.process_count()
                if self.n_shards % nproc != 0:
                    log_fatal("pre_partition requires an equal device "
                              "count per process")
                counts = np.asarray(multihost_utils.process_allgather(
                    np.asarray([N_real], np.int64))).reshape(-1)
                self._local_rows = int(N_real)
                self.global_num_data = int(counts.sum())
                # every process pads its host arrays to the same local
                # size so the global sharded array is uniform
                per = max(int(counts.max()), 1)
                self._host_pad = pad_rows_to(per, self.n_shards // nproc,
                                             multiple=lane_multiple())
                self.N_pad = self._host_pad * nproc
                log_info(
                    f"Pre-partitioned data-parallel training: rank "
                    f"{jax.process_index()}/{nproc} holds {N_real} of "
                    f"{self.global_num_data} rows; {self.n_shards} "
                    f"devices, global rows padded to {self.N_pad}")
                self._dist_guards(cfg)
            else:
                self.N_pad = pad_rows_to(N_real, self.n_shards,
                                         multiple=lane_multiple())
                self._host_pad = self.N_pad
                log_info(f"Data-parallel training over {self.n_shards} "
                         f"devices ({N_real} rows padded to "
                         f"{self.N_pad})")
        else:
            self.mesh = None
            self.n_shards = 1
            self.N_pad = N_real
            self._host_pad = N_real

        max_bin = max((m.num_bin for m in ds.mappers), default=2)
        # EFB: ship the bundled columns to the device instead of the raw
        # matrix (the serial growers don't unpack bundles; gated below)
        self._use_bundles = (ds.bundles is not None
                             and type(self).__name__ == "GBDT"
                             and cfg.tpu_grower in ("auto", "wave",
                                                    "wave_exact"))
        if self._use_bundles:
            X = ds.X_bundled
            max_bin = max(max_bin, int(X.max()) + 1)
        else:
            X = ds.X_binned
        self.num_bins_padded = max(_round_up(max_bin, 8), 8)
        self._max_bin = max_bin   # autotune cache key component (degrade
        #                           path re-pins under the same key)
        with span("booster/init/transpose"):
            Xt_np = np.ascontiguousarray(X.T)               # [F(b), N]
            if self._host_pad != N_real:
                Xt_np = np.pad(Xt_np,
                               ((0, 0), (0, self._host_pad - N_real)))
        with span("booster/init/upload", bytes_up=Xt_np.nbytes):
            with self._prof_span("bin"):
                self.X_t = self._put_rows(jnp.asarray(Xt_np), row_axis=1)
        with span("booster/init/meta"):
            self._init_feature_meta(ds, cfg)
        # per-STORAGE-COLUMN bin counts for the bin-width-tiered histogram
        # path (ops/histogram_tiered.py, docs/PERF.md): bundled storage
        # counts each bundle column's packed width, raw storage the mapper
        # widths; the dataset's tier reorder made same-width columns
        # contiguous
        if self._use_bundles:
            hist_tiers = tuple(ds.storage_num_bins())
        else:
            hist_tiers = tuple(int(m.num_bin) for m in ds.mappers)
        # the reference's layout knobs (config validation already rejected
        # contradictory combinations): force_row_wise pins the row-wise
        # multi-value kernel; force_col_wise is applied below by
        # restricting the autotune candidate set to the col-wise impls
        hist_impl_cfg = str(cfg.histogram_impl)
        if cfg.force_row_wise and hist_impl_cfg == "auto":
            hist_impl_cfg = "rowwise"
        self.grow_cfg = GrowConfig(
            num_leaves=cfg.num_leaves,
            max_depth=cfg.max_depth,
            min_data_in_leaf=float(cfg.min_data_in_leaf),
            min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
            lambda_l1=cfg.lambda_l1,
            lambda_l2=cfg.lambda_l2,
            max_delta_step=cfg.max_delta_step,
            min_gain_to_split=cfg.min_gain_to_split,
            path_smooth=cfg.path_smooth,
            num_bins_padded=self.num_bins_padded,
            rows_per_chunk=cfg.tpu_rows_per_block * 8,
            has_categorical=bool(ds.feature_is_categorical().any()),
            max_cat_to_onehot=cfg.max_cat_to_onehot,
            max_cat_threshold=cfg.max_cat_threshold,
            cat_l2=cfg.cat_l2,
            cat_smooth=cfg.cat_smooth,
            min_data_per_group=float(cfg.min_data_per_group),
            wave_exact=(cfg.tpu_grower == "wave_exact"),
            # slack >= 1 would block the top ready leaf forever (device
            # while_loop livelock); clamp below 1
            wave_gain_slack=min(max(cfg.tpu_wave_gain_slack, 0.0), 0.99),
            use_quantized_grad=cfg.use_quantized_grad,
            num_grad_quant_bins=cfg.num_grad_quant_bins,
            stochastic_rounding=cfg.stochastic_rounding,
            quant_renew_leaf=cfg.quant_train_renew_leaf,
            bundle_col=(tuple(ds.bundle_col) if self._use_bundles else ()),
            bundle_off=(tuple(ds.bundle_off) if self._use_bundles else ()),
            bundle_nb=(tuple(int(m.num_bin) for m in ds.mappers)
                       if self._use_bundles else ()),
            bundle_db=(tuple(int(m.default_bin) for m in ds.mappers)
                       if self._use_bundles else ()),
            n_shards=(self.n_shards if self.use_dist else 1),
            voting_top_k=(cfg.top_k if cfg.tree_learner == "voting"
                          and self.use_dist else 0),
            feature_fraction_bynode=float(cfg.feature_fraction_bynode),
            extra_trees=bool(cfg.extra_trees),
            extra_seed=int(cfg.extra_seed),
            monotone_method=str(cfg.monotone_constraints_method),
            monotone_penalty=float(cfg.monotone_penalty),
            feature_parallel=self._feat_par,
            hist_tiers=hist_tiers,
            hist_impl=hist_impl_cfg,
            parallel_hist_mode=str(cfg.parallel_hist_mode),
        )

        # grower selection: "wave" (default via auto) applies batched
        # gain-ordered frontier splits per histogram pass; "wave_exact"
        # keeps strict leaf-wise priority order on the wave machinery;
        # "compact"/"masked" are the serial growers. The wave paths keep
        # TWO [L, 3, F, B] histogram caches resident (own + speculated
        # smaller-child) plus ~2 [KMAX, 3, F, B] wave temporaries (the
        # reference bounds the analogous structure with
        # histogram_pool_size, serial_tree_learner.cpp:40)
        from ..ops.grow_wave import _wave_buckets
        cache_bytes = (cfg.num_leaves * len(ds.mappers)
                       * self.num_bins_padded * 3 * 4)
        wave_bytes = cache_bytes * 2 + (
            _wave_buckets(cfg.num_leaves)[-1] * len(ds.mappers)
            * self.num_bins_padded * 3 * 4) * 2
        pool_limit = (cfg.histogram_pool_size * 1024 * 1024
                      if cfg.histogram_pool_size > 0 else 512 * 1024 * 1024)
        if cfg.tpu_grower in ("compact", "masked", "wave", "wave_exact"):
            self.grower = cfg.tpu_grower
        elif wave_bytes <= pool_limit:
            self.grower = "wave"
        elif cache_bytes <= pool_limit:
            self.grower = "compact"
        else:
            self.grower = "masked"
        ladder_choice = self.grower
        # memory feasibility per strategy, reused by the autotuner below
        self._grower_feasible = ["masked"]
        if cache_bytes <= pool_limit:
            self._grower_feasible.insert(0, "compact")
        if wave_bytes <= pool_limit:
            self._grower_feasible.insert(0, "wave")
        if self._use_bundles and self.grower not in ("wave",
                                                     "wave_exact"):
            # the memory guard picked a serial grower, but X_t/meta/
            # grow_cfg were already built from the BUNDLED matrix and the
            # serial growers cannot unpack bundles — the wave grower is
            # the only valid choice here. Warn if its caches exceed the
            # configured pool (histogram_pool_size is a soft hint,
            # serial_tree_learner.cpp:40).
            fb = len(ds.bundles)
            wave_bytes_b = 2 * (cfg.num_leaves
                                + _wave_buckets(cfg.num_leaves)[-1]) \
                * fb * self.num_bins_padded * 2 * 4
            if wave_bytes_b > pool_limit:
                log_warning(
                    "EFB wave histogram caches (%.0f MB) exceed "
                    "histogram_pool_size; using the wave grower anyway"
                    % (wave_bytes_b / 1e6))
            self.grower = "wave"
        if cfg.use_quantized_grad and self.grower not in ("wave",
                                                          "wave_exact"):
            log_warning("use_quantized_grad is implemented by the wave "
                        "grower; switching tpu_grower to 'wave'")
            self.grower = "wave"
        if (self.meta.monotone is not None
                or self.meta.inter_sets is not None
                or self.meta.forced is not None
                or cfg.feature_fraction_bynode < 1.0
                or cfg.extra_trees) \
                and self.grower not in ("wave", "wave_exact"):
            log_warning("monotone/interaction/forced-split/by-node-"
                        "sampling/extra_trees features are implemented by "
                        "the wave grower; switching tpu_grower to 'wave'")
            self.grower = "wave"
        if cfg.tree_learner == "voting" and self.use_dist:
            if self.meta.forced is not None \
                    or bool(ds.feature_is_categorical().any()):
                log_fatal("tree_learner=voting does not support forced "
                          "splits or categorical features yet")
            if self._use_bundles:
                log_fatal("tree_learner=voting does not support EFB "
                          "bundling yet; set enable_bundle=false")
            if self.grower not in ("wave", "wave_exact"):
                log_warning("tree_learner=voting is implemented by the "
                            "wave grower; switching tpu_grower to 'wave'")
                self.grower = "wave"
        if self._feat_par:
            # the serial growers psum histograms — with replicated rows
            # that would overcount n_shards-fold; feature partitioning
            # lives in the wave grower only
            if self._use_bundles:
                log_fatal("tree_learner=feature does not support EFB "
                          "bundling yet; set enable_bundle=false")
            if self.grower not in ("wave", "wave_exact"):
                log_warning("tree_learner=feature is implemented by the "
                            "wave grower; switching tpu_grower to 'wave'")
                self.grower = "wave"
        # linear trees (reference: linear_tree_learner.cpp wrapping any
        # single-node learner; the parallel learners refuse it there too)
        self._linear = bool(cfg.linear_tree)
        if self._linear:
            if self.use_dist:
                log_fatal("linear_tree is not supported with distributed "
                          "tree learners (matches the reference)")
            if ds.raw_data is None:
                log_fatal(
                    "linear_tree requires raw feature values at train "
                    "time; construct the Dataset from an in-memory "
                    "matrix or text file (binary caches, Sequences and "
                    "sparse inputs do not retain raw data)")
            self._raw = ds.raw_data
            self._lin_numeric = ~ds.feature_is_categorical()
            self._lin_inner2real = np.asarray(ds.real_feature_index,
                                              np.int64)
        # CEGB (cost_effective_gradient_boosting.hpp): split + coupled
        # penalties implemented; the per-(row, feature) lazy penalty is not
        if cfg.cegb_penalty_feature_lazy:
            log_fatal("cegb_penalty_feature_lazy is not implemented in "
                      "lightgbm_tpu yet")
        self._cegb_on = (cfg.cegb_penalty_split > 0.0
                         or bool(cfg.cegb_penalty_feature_coupled))
        self._cegb_used = None
        if self._cegb_on:
            if cfg.cegb_penalty_feature_coupled:
                if len(cfg.cegb_penalty_feature_coupled) \
                        != ds.num_total_features:
                    log_fatal("cegb_penalty_feature_coupled should be the "
                              "same size as feature number.")
                cpl = np.zeros(len(ds.mappers), np.float32)
                for inner, real in enumerate(ds.real_feature_index):
                    cpl[inner] = cfg.cegb_penalty_feature_coupled[real]
                self.meta = self.meta._replace(
                    cegb_coupled=jnp.asarray(cpl))
            if self.use_dist:
                log_fatal("cegb_* is not supported with distributed "
                          "tree learners yet")
            if self.grower not in ("wave", "wave_exact"):
                log_warning("cegb_* is implemented by the wave grower; "
                            "switching tpu_grower to 'wave'")
                self.grower = "wave"
            if self._use_bundles:
                log_fatal("cegb_* with EFB bundling (enable_bundle) is "
                          "not supported; set enable_bundle=false")
            self.grow_cfg = self.grow_cfg._replace(
                cegb_tradeoff=float(cfg.cegb_tradeoff),
                cegb_penalty_split=float(cfg.cegb_penalty_split))
            self._cegb_used = jnp.zeros((len(ds.mappers),), bool)

        K = self.num_tree_per_iteration
        N = self.num_data
        md = ds.metadata

        def pad1(a):
            if a is None:
                return None
            a = np.asarray(a)
            if self._host_pad != N:
                a = np.pad(a, (0, self._host_pad - N))
            return a

        self.label_dev = (self._put_rows(jnp.asarray(pad1(md.label)))
                          if md.label is not None else None)
        self.weight_dev = (self._put_rows(jnp.asarray(pad1(md.weight)))
                           if md.weight is not None else None)

        # initial scores (Metadata::init_score, c.f. score_updater.hpp:27-47)
        scores = np.zeros((K, N), dtype=np.float32)
        if md.init_score is not None:
            init = np.asarray(md.init_score, np.float64).reshape(-1)
            scores += init.reshape(K, N) if init.size == K * N else init.reshape(1, N)
            self._has_init_score = True
        else:
            self._has_init_score = False
        if self._host_pad != N:
            scores = np.pad(scores, ((0, 0), (0, self._host_pad - N)))
        self.scores = self._put_rows(jnp.asarray(scores), row_axis=1)

        if self.objective is not None:
            self.objective.init(md, N)
        for m in self.training_metrics:
            m.init(md, N)

        # sample strategy (bagging / goss), reference: sample_strategy.cpp:16
        from .sample_strategy import create_sample_strategy
        if self._pre_part:
            # de-correlate per-rank bagging draws (each rank bags its own
            # shard; identical seeds would tie the masks row-for-row)
            import dataclasses
            cfg_bag = dataclasses.replace(
                cfg, bagging_seed=cfg.bagging_seed
                + jax.process_index() * 7919)
            self.sample_strategy = create_sample_strategy(cfg_bag, N, md)
        else:
            self.sample_strategy = create_sample_strategy(cfg, N, md)
        self._in_bag_dev = None

        # -- init-time strategy autotuning (runtime/autotune.py): the
        # reference's TrainingShareStates timing dance generalized — probe
        # the feasible growers + histogram chunk layouts on a subsample of
        # the real binned matrix and route dispatch through the winner.
        # Default off; feature-constrained configurations (anything that
        # already forced a specific grower above) keep the ladder choice.
        if cfg.autotune:
            constrained = (cfg.tpu_grower != "auto"
                           or self.grower != ladder_choice
                           or self.use_dist or self._linear)
            if constrained:
                log_warning(
                    "autotune=true ignored: the grower choice is "
                    "constrained (forced tpu_grower, distributed/linear "
                    "mode, or a feature only the wave grower implements)")
                # the histogram-EXCHANGE mode is still a free variable on
                # a data-parallel mesh: probe allreduce vs reduce_scatter
                # at the real payload shape (both produce bit-identical
                # trees, so this only tunes the wire profile)
                if (self.use_dist and not self._feat_par
                        and cfg.tree_learner in ("data", "data_parallel")
                        and cfg.parallel_hist_mode == "auto"):
                    from ..runtime.autotune import autotune_comm_decision
                    with self._prof_span("autotune"):
                        comm = autotune_comm_decision(
                            self.mesh,
                            n_rows=self.num_data,
                            n_features=int(self.X_t.shape[0]),
                            max_bin=max_bin,
                            num_leaves=cfg.num_leaves,
                            num_bins_padded=self.num_bins_padded,
                            cache_path=cfg.autotune_cache,
                            seed=int(cfg.seed or 0))
                    self.autotune_decision = comm
                    mode = comm.get("parallel_hist_mode")
                    if mode:
                        log_info("autotune: comm probe picked "
                                 f"parallel_hist_mode='{mode}'")
                        self.grow_cfg = self.grow_cfg._replace(
                            parallel_hist_mode=str(mode))
                    if self.profiler is not None:
                        self.profiler.extras["autotune_comm"] = comm
            else:
                from ..runtime.autotune import (COL_WISE_HIST_IMPLS,
                                                autotune_decision)
                with self._prof_span("autotune"):
                    decision = autotune_decision(
                        self.X_t, self.meta, self.grow_cfg,
                        self._grower_feasible,
                        n_rows=self.num_data,
                        n_features=len(ds.mappers),
                        max_bin=max_bin,
                        num_leaves=cfg.num_leaves,
                        cache_path=cfg.autotune_cache,
                        seed=int(cfg.seed or 0),
                        hist_impl_candidates=(COL_WISE_HIST_IMPLS
                                              if cfg.force_col_wise
                                              else None))
                self.autotune_decision = decision
                if decision.get("grower"):
                    if decision["grower"] != self.grower:
                        log_info(
                            "autotune: probes picked grower "
                            f"'{decision['grower']}' over ladder choice "
                            f"'{self.grower}'")
                    self.grower = decision["grower"]
                rc = int(decision.get("rows_per_chunk", 0) or 0)
                if rc > 0 and rc != self.grow_cfg.rows_per_chunk:
                    self.grow_cfg = self.grow_cfg._replace(
                        rows_per_chunk=rc)
                hist_impl = decision.get("hist_impl")
                if hist_impl in ("rowwise", "rowwise_packed") \
                        and cfg.force_col_wise:
                    # a decision cached by an unconstrained run; the
                    # layout pin outranks it
                    hist_impl = None
                if hist_impl and hist_impl != self.grow_cfg.hist_impl:
                    log_info("autotune: probes picked histogram impl "
                             f"'{hist_impl}'")
                    self.grow_cfg = self.grow_cfg._replace(
                        hist_impl=str(hist_impl))
                if self.profiler is not None:
                    self.profiler.extras["autotune"] = decision

        if self.profiler is not None and self.grow_cfg.hist_tiers:
            self._profile_hist_tiers()

        # analytic histogram-exchange wire profile (docs/PERF.md
        # §Communication): fixed for the whole run once the grower and
        # parallel_hist_mode are settled, attached to every iteration
        # record by train_one_iter
        self._comm_profile = self._comm_iter_profile()
        if self.profiler is not None and self._comm_profile:
            self.profiler.extras["comm"] = dict(self._comm_profile)

        self._build_jit_fns()

    def _profile_hist_tiers(self) -> None:
        """Record the dataset's width-class structure and one stage span
        per class (hist_class_b{lane}) so device_profile output shows how
        the histogram pass splits across bin-width tiers (docs/PERF.md).
        Probes a row subsample of the resident binned matrix; skipped on
        meshes (X_t is sharded and the probe would only fence shard 0)."""
        from ..ops.histogram import build_histogram
        from ..ops.histogram_rowwise import (build_pack4_plan,
                                             build_rowwise_plan,
                                             pack4_worthwhile,
                                             rowwise_eligible)
        from ..ops.histogram_tiered import build_tier_plan
        if max(self.grow_cfg.hist_tiers) > 256:
            return          # uint16 storage: no Pallas path, no tiers
        tiers = tuple(int(t) for t in self.grow_cfg.hist_tiers)
        plan = build_tier_plan(tiers)
        self.profiler.extras["hist_tiers"] = [
            {"start": s, "count": c, "lane_bins": w}
            for (s, c, w) in plan.classes]
        self.profiler.extras["hist_impl"] = self.grow_cfg.hist_impl
        rplan = build_rowwise_plan(tiers)
        self.profiler.extras["hist_rowwise"] = {
            "flat_cols": rplan.total,
            "col_wise_cols": sum(c * w for (_, c, w) in plan.classes),
            "chunks": len(rplan.chunks)}
        pplan = build_pack4_plan(tiers)
        self.profiler.extras["hist_pack4"] = {
            "n_packed": pplan.n_packed,
            "n_rest": pplan.n_rest,
            # binned-operand stream bytes vs the unpacked storage matrix
            "stream_frac": round(
                (((pplan.n_packed + 1) // 2) + max(pplan.n_rest, 1))
                / max(len(tiers), 1), 4)}
        if self.use_dist:
            return
        n_probe = int(min(self.N_pad, 65536))
        vals = jnp.ones((2, n_probe), jnp.float32)
        for (s, c, w) in plan.classes:
            with self._prof_span(f"hist_class_b{w}"):
                build_histogram(self.X_t[s:s + c, :n_probe], vals,
                                min(self.num_bins_padded, w))
        if rowwise_eligible(rplan, 2, 1):
            with self._prof_span("hist_rowwise"):
                build_histogram(self.X_t[:, :n_probe], vals,
                                self.num_bins_padded, tiers=tiers,
                                impl="rowwise")
            if pack4_worthwhile(pplan):
                with self._prof_span("hist_rowwise_packed"):
                    build_histogram(self.X_t[:, :n_probe], vals,
                                    self.num_bins_padded, tiers=tiers,
                                    impl="rowwise_packed")

    def _comm_iter_profile(self) -> Optional[Dict[str, Any]]:
        """Analytic on-wire byte count of the per-tree histogram exchange
        (docs/PERF.md §Communication payload math). The grower is one
        fused jit, so the host cannot fence-time individual collectives;
        what it CAN state exactly is the payload shape, the exchange
        count bound (one [2,F,B] root pass plus one child exchange per
        split) and the ring-algorithm wire factor — 2(k-1)/k for a full
        psum, (k-1)/k for psum_scatter. Packed quantized lanes halve the
        channel count (parallel/packed.py). Returns None when training
        is not data-parallel (nothing crosses the mesh axis per split)."""
        if not self.use_dist or self._feat_par:
            return None
        from ..utils import round_up
        gcfg = self.grow_cfg
        k = int(self.n_shards)
        F = int(self.X_t.shape[0])
        B = int(gcfg.num_bins_padded)
        L = int(gcfg.num_leaves)
        wave = self.grower in ("wave", "wave_exact")
        mode = str(gcfg.parallel_hist_mode)
        if mode == "auto":
            # each grower's default exchange (ops/grow.py, grow_wave.py)
            mode = "reduce_scatter" if wave else "allreduce"
        Fx = round_up(F, k) if mode == "reduce_scatter" else F
        packed = False
        if wave:
            channels = 2          # (grad, hess) lanes, f32 or int32
            if gcfg.use_quantized_grad:
                from ..parallel.packed import pack_safe
                packed = bool(pack_safe(self.N_pad,
                                        gcfg.num_grad_quant_bins))
                if packed:
                    channels = 1  # int32-packed-int16 pair
            elems = (1 + (L - 1)) * channels * Fx * B
        else:
            # serial grower: root [2,F,B], then one fused both-children
            # [4,F,B] pass per remaining split (ops/grow.py)
            elems = (2 + 4 * max(L - 2, 0)) * Fx * B
        factor = (k - 1) / k * (1.0 if mode == "reduce_scatter" else 2.0)
        return {
            "comm_mode": mode,
            "comm_packed": packed,
            "mesh_size": k,
            "comm_bytes_per_tree": int(elems * 4 * factor),
        }

    def _init_feature_meta(self, ds: BinnedDataset, cfg: Config) -> None:
        """Per-feature device metadata: bins, missing types, constraints,
        forced splits and the EFB expansion tables."""
        self.meta = build_feature_meta(ds, cfg.monotone_constraints,
                                       cfg.interaction_constraints)
        if cfg.forcedsplits_filename:
            forced_tbl = parse_forced_splits(cfg.forcedsplits_filename, ds)
            if forced_tbl is not None:
                self.meta = self.meta._replace(
                    forced=jnp.asarray(forced_tbl))
        if self._use_bundles:
            F = len(ds.mappers)
            B = self.num_bins_padded
            expand = np.full((F, B), len(ds.bundles) * B, np.int32)  # fill
            mfb = np.zeros((F, B), np.float32)
            for f, m in enumerate(ds.mappers):
                ci, off = ds.bundle_col[f], ds.bundle_off[f]
                dbf, nbf = m.default_bin, m.num_bin
                mfb[f, dbf] = 1.0
                for b in range(nbf):
                    if off < 0:
                        expand[f, b] = ci * B + b
                    elif b != dbf:
                        expand[f, b] = ci * B + off + b - (1 if b > dbf
                                                           else 0)
            self.meta = self.meta._replace(
                bundle_expand=jnp.asarray(expand.reshape(-1)),
                bundle_mfb=jnp.asarray(mfb))
        if self.meta.monotone is not None \
                and cfg.monotone_constraints_method not in (
                    "basic", "intermediate"):
            log_fatal("monotone_constraints_method="
                      f"{cfg.monotone_constraints_method} is not "
                      "implemented (use 'basic' or 'intermediate')")

    def _prof_span(self, name: str):
        """The active profiler's span, or a no-op context."""
        return (self.profiler.span(name) if self.profiler is not None
                else contextlib.nullcontext())

    def _put_rows(self, arr: jnp.ndarray, row_axis: int = 0) -> jnp.ndarray:
        """Shard `arr` rows over the mesh data axis (no-op when serial).
        Pre-partitioned mode assembles the GLOBAL sharded array from each
        process's local rows (no process ever holds the full data);
        feature-parallel mode REPLICATES rows (features partition
        instead)."""
        if not self.use_dist:
            return arr
        if self._feat_par:
            from ..parallel.data_parallel import replicated
            return replicated(self.mesh, arr)
        if self._pre_part:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from ..parallel import DATA_AXIS
            spec = [None] * np.ndim(arr)
            spec[row_axis] = DATA_AXIS
            return jax.make_array_from_process_local_data(
                NamedSharding(self.mesh, P(*spec)), np.asarray(arr))
        from ..parallel import shard_rows
        return shard_rows(self.mesh, arr, row_axis=row_axis)

    def _dist_guards(self, cfg: Config) -> None:
        """Features whose host paths assume the full dataset on one
        process fail loudly under pre-partitioned loading (matching the
        reference's parallel-learner restrictions)."""
        if self.objective is not None and (
                self.objective.runs_on_host
                or self.objective.need_renew_tree_output):
            log_fatal("pre_partition supports device-side objectives "
                      "without leaf renewal only (got "
                      f"{cfg.objective})")
        if cfg.boosting in ("dart", "rf"):
            log_fatal("pre_partition does not support boosting="
                      f"{cfg.boosting} yet")

    def _local_scores(self, k: int) -> np.ndarray:
        """This process's rows of scores[k] (pre-partitioned mode),
        padding stripped."""
        shards = sorted(self.scores.addressable_shards,
                        key=lambda s: s.index[1].start
                        if s.index[1].start is not None else 0)
        local = np.concatenate([np.asarray(sh.data) for sh in shards],
                               axis=1)
        return local[k, :self._local_rows]

    def _build_jit_fns(self) -> None:
        """The trainer's jitted programs. None of them closes over an
        array derived from the data: the dataset's per-feature facts
        (`self.meta`) are an ARGUMENT of every one, so the lowered text,
        and with it the persistent compile cache's key, is a function of
        shapes, `GrowConfig` and the None-pattern of `FeatureMeta` alone
        (docs/PERF.md §7; tests/test_compile_reuse.py holds it)."""
        cfg_static = self.grow_cfg

        if self.grower in ("wave", "wave_exact"):
            from ..ops.grow_wave import grow_tree_wave as grow_fn
        elif self.grower == "compact":
            from ..ops.grow_fast import grow_tree_fast as grow_fn
        else:
            grow_fn = grow_tree

        takes_seed = self.grower in ("wave", "wave_exact")
        if self.use_dist:
            from ..parallel import build_data_parallel_train_fn
            self._train_tree = build_data_parallel_train_fn(
                self.mesh, cfg_static, grow_fn=grow_fn,
                replicate_rows=self._feat_par)
        else:
            cegb_on = self._cegb_on

            @jax.jit
            def train_tree(X_t, grad, hess, in_bag, scores_k, lr,
                           feat_mask, seed, meta, used):
                kw = dict(feature_mask=feat_mask)
                if takes_seed:
                    kw["rng_seed"] = seed
                if cegb_on:
                    kw["cegb_used"] = used
                tree, leaf_of_row = grow_fn(
                    X_t, grad, hess, in_bag, meta, cfg_static, **kw)
                from ..ops.histogram import take_leaf_values
                with jax.named_scope("train/score_update"):
                    new_scores = scores_k + take_leaf_values(
                        tree.leaf_value * lr, leaf_of_row)
                # CEGB coupled-penalty state: features used by this tree
                # (UpdateLeafBestSplits flips is_feature_used_in_split_,
                # cost_effective_gradient_boosting.hpp:110)
                if cegb_on:
                    m = jnp.arange(tree.split_feature.shape[0]) \
                        < tree.num_leaves - 1
                    used = used.at[jnp.where(
                        m, tree.split_feature, used.shape[0])].set(
                        True, mode="drop")
                return tree, leaf_of_row, new_scores, used

            self._train_tree_core = train_tree

            def train_tree_wrap(*args):
                # `_cegb_used` is None unless CEGB is on, and CEGB never
                # trains in a scan (can_batch_iters): the state is read
                # here per call, on the host, and never at trace time
                tree, lor, scores, used = train_tree(*args,
                                                     self._cegb_used)
                if cegb_on:
                    self._cegb_used = used
                return tree, lor, scores

            self._train_tree = train_tree_wrap

        # a table without categorical columns grows no bitset split: the
        # walk is told so statically (structure, like a None of
        # FeatureMeta), which frees it to take the path-matrix walk
        has_cat = any(m.bin_type == BIN_TYPE_CATEGORICAL
                      for m in self.mappers)

        @jax.jit
        def valid_update(split_feature, threshold_bin, default_left,
                         left_child, right_child, num_leaves, leaf_value,
                         Xv_t, vmeta_arrs, scores_k, lr, split_is_cat,
                         split_cat_bitset):
            vmeta = FeatureMeta(*vmeta_arrs)
            if not has_cat:
                split_is_cat = split_cat_bitset = None
            with jax.named_scope("lgbm_valid_update"):
                leaf = predict_leaf_binned(
                    split_feature, threshold_bin, default_left, left_child,
                    right_child, num_leaves, Xv_t, vmeta, split_is_cat,
                    split_cat_bitset)
                return scores_k + (leaf_value * lr)[leaf]

        self._valid_update = valid_update

        if self.objective is not None and not self.objective.runs_on_host:
            obj = self.objective

            @jax.jit
            def grad_fn(scores, label, weight, ostate):
                if obj.num_model_per_iteration == 1:
                    g, h = _gradients(obj, scores[0], label, weight, ostate)
                    return g[None, :], h[None, :]
                return _gradients(obj, scores, label, weight, ostate)

            self._grad_fn = grad_fn
        else:
            self._grad_fn = None

    # ------------------------------------------------------------------
    def add_valid_dataset(self, ds: BinnedDataset, name: str,
                          metrics: Sequence[Metric]) -> None:
        Xv = ds.X_binned
        self._valid_Xt.append(jnp.asarray(np.ascontiguousarray(Xv.T)))
        self._valid_meta.append(self.meta)
        K = self.num_tree_per_iteration
        scores = np.zeros((K, ds.num_data), dtype=np.float32)
        if ds.metadata.init_score is not None:
            init = np.asarray(ds.metadata.init_score, np.float64).reshape(-1)
            scores += init.reshape(K, -1) if init.size == K * ds.num_data \
                else init.reshape(1, -1)
        # replay already-trained model (continued training)
        if self.models:
            for it, tree in enumerate(self.models):
                k = it % self.num_tree_per_iteration
                leaf = tree.get_leaf_binned(Xv, self)
                scores[k] += self._tree_output(tree, self._raw_or_none(ds),
                                               leaf)
        self._valid_scores.append(jnp.asarray(scores))
        self.valid_sets.append(ds)
        self.valid_names.append(name)
        for m in metrics:
            m.init(ds.metadata, ds.num_data)
            m.device_state()      # built here, once, not at the first chunk
        # in-scan eval state (docs/PERF.md §7): the batched path computes
        # these metrics on device inside the boosting scan, so it needs
        # device-resident label/weight and the metric objects themselves
        self._valid_metrics.append(list(metrics))
        md = ds.metadata
        self._valid_label_dev.append(
            jnp.asarray(np.asarray(md.label, np.float32))
            if md.label is not None else None)
        if md.weight is not None:
            w = np.asarray(md.weight, np.float32)
            self._valid_weight_dev.append(jnp.asarray(w))
            self._valid_sumw.append(float(np.sum(w)))
        else:
            self._valid_weight_dev.append(
                jnp.ones((ds.num_data,), jnp.float32))
            self._valid_sumw.append(float(ds.num_data))

    # ------------------------------------------------------------------
    @property
    def models(self) -> List[Tree]:
        """Host trees; materializes any pending device trees first."""
        self._materialize_models()
        return self._models

    def _materialize_models(self) -> None:
        if not self._pending and (self._drain is None
                                  or self._drain.idle()):
            return
        with span("train/drain"):
            n0 = len(self._models)
            if self._drain is not None:
                with span("train/drain/flush_worker"):
                    self._drain.flush()
            pending, self._pending = self._pending, []
            # one batched transfer for all pending trees (one host sync).
            # Records are either a single DeviceTree (bias: float) or a
            # chunk of trees stacked [n, K, ...] (bias: list,
            # iteration-major).
            if pending:
                with span("train/drain/device_get"):
                    hosts = jax.device_get([t for t, _ in pending])
                    span_count(bytes_down=sum(
                        a.nbytes for a in jax.tree.leaves(hosts)))
                with span("train/drain/to_trees"):
                    for host, (_, bias) in zip(hosts, pending):
                        self._models.extend(
                            self._host_record_to_trees(host, bias))
            span_count(trees=len(self._models) - n0)

    def _host_record_to_trees(self, host, bias) -> List[Tree]:
        """Convert one device_get'd pending record (single tree or a
        stacked [n, K, ...] chunk) into host Trees. The bias list length
        is authoritative for chunk records: padded tail-chunk rows (when
        the scan ran n_pad > n iterations) carry no bias entry and are
        never materialized."""
        K = self.num_tree_per_iteration
        if isinstance(bias, list):
            flat = [jax.tree.map(
                lambda a, i=i, k=k: a[i, k], host)
                for i in range(len(bias) // K)
                for k in range(K)]
        else:
            flat = [host]
            bias = [bias]
        sp = self._chunk_spans.pop(id(bias), None)
        out = []
        for h, b in zip(flat, bias):
            tree = self._device_tree_to_host(h)
            if abs(b) > _KEPS:
                tree.add_bias(b)
            out.append(tree)
        if sp is not None:
            # the chunk's categorical splits, on its train/chunk span
            sp.counts["cat_splits"] = sum(int(t.num_cat) for t in out)
            wc = [h.wave_counts for h in flat
                  if h.wave_counts is not None]
            if wc:
                # the wave megakernel's calls, slots and contracted rows
                tot = np.sum(np.asarray(wc, np.int64), axis=0)
                for key, v in zip(("waves", "wave_slots", "hist_rows"),
                                  tot):
                    sp.counts[key] = int(v)
        return out

    def _check_stopped(self) -> bool:
        """Fetch the pending trees' leaf counts (one sync) and report
        whether the last iteration produced only stumps (reference stop
        condition, gbdt.cpp:376-384)."""
        if self._drain is not None:
            # drained chunks land in _models; flush so the _models[-K:]
            # branch below sees the latest iteration
            self._drain.flush()
        K = self.num_tree_per_iteration
        if self._pending:
            # gather the last K tree leaf-counts in ONE batched transfer
            # (records may be single trees or stacked chunks)
            take, need = [], K
            for trees, _ in reversed(self._pending):
                take.append(trees.num_leaves)
                need -= int(np.prod(np.shape(trees.num_leaves)) or 1)
                if need <= 0:
                    break
            with span("train/stop_check"):   # waits for the device
                got = jax.device_get(take)
            counts = [c for g in reversed(got)
                      for c in np.asarray(g).reshape(-1)][-K:]
        elif self._models:
            counts = [t.num_leaves for t in self._models[-K:]]
        else:
            return False
        if all(int(c) <= 1 for c in counts):
            log_warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            return True
        return False

    # ------------------------------------------------------------------
    def boost(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Compute gradients from current scores (GBDT::Boosting,
        gbdt.cpp:229)."""
        if self.objective is None:
            log_fatal("No objective function provided for boosting")
        if self.objective.runs_on_host:
            # NOTE(multi-host): device_get on a row-sharded array only works
            # when all shards are process-addressable (single-host meshes).
            # The multi-host runner will keep host reads per-process-local
            # (each process computes gradients for its own row shard, like
            # the reference's per-rank Metadata) — tracked for round 2.
            score_np = np.asarray(
                jax.device_get(self.scores))[:, :self.num_data]
            g, h = self.objective.get_gradients_numpy(score_np.reshape(-1))
            K = self.num_tree_per_iteration
            g = g.reshape(K, -1)
            h = h.reshape(K, -1)
            if self._host_pad != self.num_data:
                pad = ((0, 0), (0, self._host_pad - self.num_data))
                g = np.pad(g, pad)
                h = np.pad(h, pad)
            return (self._put_rows(jnp.asarray(g), row_axis=1),
                    self._put_rows(jnp.asarray(h), row_axis=1))
        return self._grad_fn(self.scores, self.label_dev, self.weight_dev,
                             self.objective.device_state())

    # ------------------------------------------------------------------
    # batched training: host-free boosting chunks (docs/PERF.md §7)
    # ------------------------------------------------------------------
    _SCAN_CACHE_MAX = 4   # bounded LRU over (chunk, metric, mode) keys

    def _count_dispatch(self, n: int = 1) -> None:
        """Count jitted host->device dispatches — the number
        bench_batched.py divides by iterations; mirrored into the
        profiler counters when device_profile is on."""
        self.dispatch_count += n
        if self.profiler is not None:
            self.profiler.add_counter("dispatches", n)

    def _batched_sampling_mode(self) -> str:
        """'scan' = the in-bag mask is drawn inside the scan body as a
        pure function of the iteration (device-side bagging/GOSS);
        'host' = a window-constant mask is passed in, as before."""
        strat = self.sample_strategy
        if strat.supports_scan and not self.use_dist \
                and (strat.resample_period() > 0 or strat.needs_grad):
            return "scan"
        return "host"

    def _device_metric_layout(self):
        """[(vi, metric, device_fn)] covering EVERY valid-set metric, or
        None when any metric lacks a device analog (the batched path then
        defers to per-iteration host eval). Order defines the metric
        column layout of train_iters_batched's stacked values: a metric
        takes one column per result it reports (``result_names()``)."""
        out = []
        for vi, metrics in enumerate(self._valid_metrics):
            for m in metrics:
                fn = m.device_eval_fn(self.objective)
                if fn is None:
                    return None
                out.append((vi, m, fn))
        return out

    def batched_eval_layout(self):
        """(valid_name, metric_result_name, higher_better) per metric
        column of the in-scan metric stack — the engine reconstructs
        per-iteration evaluation_result_lists from this. None when some
        metric has no device analog."""
        lay = self._device_metric_layout()
        if lay is None:
            return None
        return [(self.valid_names[vi], name, m.is_higher_better)
                for vi, m, _ in lay for name in m.result_names()]

    def can_batch_iters(self, n: int) -> bool:
        """Whether `n` whole-chunk device iterations (train_iters_batched)
        are semantically equivalent to repeated train_one_iter calls.
        Batched is the DEFAULT for realistic configs: device-side
        bagging/GOSS and in-scan valid eval run inside the scan, so
        resampling and valid sets no longer force the per-iteration
        path. O(1) — the cached per-strategy resample period replaces
        the old per-iteration resamples_at probe loop."""
        if type(self) is not GBDT:
            return False          # DART/RF override per-iter behavior
        if not self.config.batched_train or os.environ.get(
                "LIGHTGBM_TPU_DISABLE_BATCHED", "") not in ("", "0"):
            return False          # escape hatches (config knob + env)
        if self.num_tree_per_iteration != 1:
            # multiclass (K > 1) stays per-iteration: compiling K tree
            # grows into one program lets XLA partition the histogram
            # reductions differently than the standalone-jitted grow,
            # and the reassociated f32 sums break the md5 parity
            # guarantee by ULPs (observed on CPU; program-shape
            # sensitive, not controllable from JAX)
            return False
        if self._linear:
            return False          # per-tree host ridge fits
        if self.objective is None or self.objective.runs_on_host:
            return False
        if self.objective.need_renew_tree_output:
            return False          # leaf renewal is a per-iteration host op
        if self._cegb_on:
            return False          # coupled-penalty state is carried across
        #                           iterations outside the scan
        if self._fault_plan is not None:
            return False          # kill@iter / collective faults fire in
        #                           train_one_iter's watchdog only
        strat = self.sample_strategy
        if self._batched_sampling_mode() == "host":
            if strat.needs_grad:
                return False      # gradient-aware masks can't be pre-drawn
            # window-constant masks only: a resample strictly inside
            # (iter, iter+n) would need a host boundary. The window
            # (iter+1 .. iter+n-1) contains a multiple of the period p
            # iff the floor-quotient advances.
            p = strat.resample_period()
            if p > 0 and (self.iter + n - 1) // p > self.iter // p:
                return False
        if self.valid_sets:
            if self.use_dist or self._pre_part:
                return False      # valid replay/averaging is host-side
            if self._device_metric_layout() is None:
                return False      # a metric lacks a device analog
        return True

    def train_iters_batched(self, n: int, n_pad: Optional[int] = None
                            ) -> Optional[jnp.ndarray]:
        """Run `n` boosting iterations as ONE jitted lax.scan — no host
        round-trips at all (the reference's TrainOneIter loop,
        gbdt.cpp:246-265, with the per-iteration host boundary removed).
        Caller must have checked can_batch_iters().

        When ``n_pad > n`` the scan still runs n_pad steps — every chunk
        reuses ONE compiled fn regardless of tail size — with the
        surplus steps inert (score updates masked out, trees sliced off
        on device). Scan-capable sample strategies draw their in-bag
        mask INSIDE the body from iteration-keyed jax.random streams,
        bit-identical to the eager mask for the same iteration; valid
        scores and metrics update in-scan too. Returns the stacked
        per-iteration metric values as a [n, M] device array (columns =
        batched_eval_layout()), or None when no valid metrics ride
        along."""
        n_pad = max(n, int(n_pad or n))
        d0 = self.dispatch_count
        # cat_splits is filled in when the chunk's trees reach the host
        # (the drain's own transfer, _host_record_to_trees): no new sync
        with span("train/chunk", trees=n, trees_padded=n_pad, cat_splits=0,
                  wave_route=self._wave_route() or "none") as sp:
            self._chunk_span = sp
            mvals = self._train_chunk(n, n_pad)
            self._chunk_span = None
            span_count(dispatches=self.dispatch_count - d0,
                       valid_rows=sum(v.num_data for v in self.valid_sets),
                       metric_columns=0 if mvals is None
                       else int(mvals.shape[-1]),
                       wave_decide_trees=self._wave_decide_trees(n))
        return mvals

    def _train_chunk(self, n: int, n_pad: int) -> Optional[jnp.ndarray]:
        K = self.num_tree_per_iteration
        prof = self.profiler
        t0 = None
        if prof is not None:
            from ..runtime.profiler import device_barrier
            device_barrier()
            t0 = time.perf_counter()
        with span("train/chunk/prepare"):
            init_scores = np.zeros(K)
            if self.iter == 0:
                init_scores = self._boost_from_average()
            mode = self._batched_sampling_mode()
            if mode == "host":
                if self._in_bag_dev is None \
                        or self.sample_strategy.resamples_at(self.iter):
                    in_bag = self.sample_strategy.sample(
                        self.iter, None, None)
                    if self._host_pad != self.num_data:
                        in_bag = jnp.pad(
                            in_bag, (0, self._host_pad - self.num_data))
                    self._in_bag_dev = self._put_rows(in_bag, row_axis=0)
                in_bag0 = self._in_bag_dev
            else:
                # drawn in-scan; a constant placeholder keeps the
                # compiled fn's arg pytree identical across chunks
                in_bag0 = getattr(self, "_in_bag_ones", None)
                if in_bag0 is None or in_bag0.shape[0] != self._host_pad:
                    in_bag0 = self._in_bag_ones = jnp.ones(
                        (self._host_pad,), jnp.float32)

            # per-iteration feature masks, precomputed host-side (same
            # RNG stream as the per-iteration path); padded steps reuse
            # an all-ones mask (their trees are discarded)
            F = len(self.mappers)
            masks_dev = jnp.stack(
                [m if m is not None else jnp.ones((F,), bool)
                 for m in (self._feature_mask_for_iter(self.iter + i)
                           for i in range(n))]
                + [jnp.ones((F,), bool)] * (n_pad - n))

        with span("train/chunk/scan_fn"):
            scan_fn = self._get_scan_fn(n_pad, mode)
        self._count_dispatch()
        with span("train/chunk/dispatch"):
            new_scores, new_vscores, tree_stack, mvals = scan_fn(
                self.X_t, self.scores, self.label_dev, self.weight_dev,
                in_bag0, jnp.float32(self.shrinkage_rate),
                jnp.int32(self.iter), jnp.int32(n), masks_dev, self.meta,
                tuple(self._valid_Xt),
                tuple(tuple(m) for m in self._valid_meta),
                tuple(self._valid_scores),
                tuple(self._valid_label_dev),
                tuple(self._valid_weight_dev),
                tuple(jnp.float32(s) for s in self._valid_sumw),
                self.objective.device_state(),
                tuple(m.device_state() for _, m, _ in
                      self._device_metric_layout() or ()))
        with span("train/chunk/submit"):
            self.scores = new_scores
            for vi, vs in enumerate(new_vscores):
                self._valid_scores[vi] = vs
            if n < n_pad:
                # tail chunk: drop the inert steps' trees/metrics on
                # device so pending stacks and stop checks never see
                # padding rows
                tree_stack = jax.tree.map(lambda a: a[:n], tree_stack)
                mvals = mvals[:n]
                self._count_dispatch()
            # ONE stacked pending record for the whole chunk (slicing
            # happens host-side at materialization — per-tree device
            # slices would reintroduce hundreds of dispatches);
            # iteration-0 bias folds into the first tree. With the async
            # drain active, the record goes to the worker so host
            # conversion overlaps the NEXT chunk's device compute.
            biases = [
                float(init_scores[k]) if (self.iter + i) == 0 else 0.0
                for i in range(n) for k in range(K)]
            record = (tree_stack, biases)
            if self._chunk_span is not None:
                self._chunk_spans[id(biases)] = self._chunk_span
            if self._drain is not None:
                self._drain.submit(record)
            else:
                self._pending.append(record)
        self.iter += n
        if prof is not None:
            from ..runtime.profiler import device_barrier
            device_barrier()   # fence: the span covers this chunk only
            prof.record_batched_chunk(n, time.perf_counter() - t0,
                                      n_rows=self.num_data * n)
        return mvals if int(mvals.shape[-1]) > 0 else None

    def _get_scan_fn(self, n_pad: int, mode: str):
        """Compiled whole-chunk scan, cached on the PADDED chunk size (so
        varying tail sizes don't retrace), the sampling mode, and the
        valid/metric signature. The cache is a bounded LRU: unbounded
        growth across chunk-size changes would pin stale executables."""
        K = self.num_tree_per_iteration
        metric_layout = self._device_metric_layout() or []
        metric_sig = tuple((vi, type(m).__name__, tuple(m.result_names()))
                           for vi, m, _ in metric_layout)
        key = (n_pad, K, mode, len(self.valid_sets), metric_sig)
        cache = getattr(self, "_scan_fns", None)
        if cache is None:
            cache = self._scan_fns = collections.OrderedDict()
        if key in cache:
            cache.move_to_end(key)
            return cache[key]
        obj = self.objective
        train_tree = self._train_tree
        valid_upd = self._valid_update
        strat = self.sample_strategy
        n_valid = len(self.valid_sets)
        metric_fns = [(vi, fn) for vi, _, fn in metric_layout]
        base_seed = self.config.seed or 0
        host_pad, num_data = self._host_pad, self.num_data

        @jax.jit
        def scan_fn(X_t, scores0, label, weight, in_bag0, lr, start_iter,
                    n_active, masks, meta, vXts, vmetas, vscores0, vlabels,
                    vweights, vsumw, ostate=None, mstates=()):
            def step(carry, xs):
                scores, vscores = carry
                mask, i = xs
                it = start_iter + i
                active = i < n_active
                with jax.named_scope("train/gradients"):
                    if K == 1:
                        g, h = _gradients(obj, scores[0], label, weight,
                                          ostate)
                        g, h = g[None, :], h[None, :]
                    else:
                        g, h = _gradients(obj, scores, label, weight,
                                          ostate)
                if mode == "scan":
                    # device-side bagging/GOSS: pure function of `it`
                    # (+ this step's gradients for GOSS), bit-identical
                    # to the eager sample() for the same iteration
                    bag = strat.mask_for_iter(it, g, h)
                    if host_pad != num_data:
                        bag = jnp.pad(bag, (0, host_pad - num_data))
                else:
                    bag = in_bag0
                new_scores = scores
                new_vscores = list(vscores)
                trees = []
                for k in range(K):
                    seed = (it + base_seed) * K + k
                    tree, _, ns = train_tree(
                        X_t, g[k], h[k],
                        bag if bag.ndim == 1 else bag[k],
                        new_scores[k], lr, mask, seed, meta)
                    new_scores = new_scores.at[k].set(ns)
                    trees.append(tree)
                    for vi in range(n_valid):
                        new_vscores[vi] = new_vscores[vi].at[k].set(
                            valid_upd(
                                tree.split_feature, tree.threshold_bin,
                                tree.default_left, tree.left_child,
                                tree.right_child, tree.num_leaves,
                                tree.leaf_value, vXts[vi], vmetas[vi],
                                new_vscores[vi][k], lr,
                                tree.split_is_cat, tree.split_cat_bitset))
                # padded tail steps are inert: carried state keeps its
                # value; their (garbage) trees are sliced off on device
                new_scores = jnp.where(active, new_scores, scores)
                new_vscores = tuple(
                    jnp.where(active, nv, ov)
                    for nv, ov in zip(new_vscores, vscores))
                if metric_fns:
                    # one column per result: a scalar metric gives one,
                    # a vector metric (ndcg@k) one per entry; a metric's
                    # own device state rides in as an argument
                    mvals = jnp.concatenate([
                        jnp.reshape(
                            fn(new_vscores[vi], vlabels[vi], vweights[vi],
                               vsumw[vi], *(() if st is None else (st,))),
                            (-1,))
                        for (vi, fn), st in zip(
                            metric_fns,
                            mstates or (None,) * len(metric_fns))])
                else:
                    mvals = jnp.zeros((0,), jnp.float32)
                stacked = jax.tree.map(lambda *a: jnp.stack(a), *trees)
                return (new_scores, new_vscores), (stacked, mvals)

            (scores, vscores), (tree_stack, mvals) = jax.lax.scan(
                step, (scores0, tuple(vscores0)),
                (masks, jnp.arange(n_pad, dtype=jnp.int32)))
            return scores, vscores, tree_stack, mvals

        cache[key] = scan_fn
        while len(cache) > self._SCAN_CACHE_MAX:
            cache.popitem(last=False)
        return scan_fn

    def start_drain(self) -> None:
        """Attach an async tree drain: chunk records produced by
        train_iters_batched are device_get'd and converted to host Trees
        on a worker thread, overlapping host materialization with the
        next chunk's device compute (double-buffering). Idempotent."""
        if self._drain is not None:
            return
        # fold any per-iteration leftovers in first so _models stays
        # ordered once drained chunks start appending
        self._materialize_models()
        self._drain = _AsyncTreeDrain(self)

    def stop_drain(self) -> None:
        """Detach and join the drain worker, folding everything it
        converted into _models. Safe to call repeatedly / without
        start_drain."""
        drain, self._drain = self._drain, None
        if drain is not None:
            drain.close()

    def truncate_to_iteration(self, n_iters: int) -> None:
        """Drop trees beyond the first `n_iters` iterations — the
        retroactive arm of batched early stopping. Exact because later
        trees never affect earlier iterations' metrics: cutting the model
        back to the stop point yields byte-identical trees to having
        stopped live. `self.scores`/valid scores intentionally keep the
        surplus contributions (training is over; predictions use the
        materialized model, and warm-continue from a truncated model goes
        through model I/O which rebuilds scores)."""
        self._materialize_models()
        keep = n_iters * self.num_tree_per_iteration
        if keep < len(self._models):
            del self._models[keep:]
        self.iter = min(self.iter, n_iters)
        self._packed_cache = None
        self._device_tables_cache = None

    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration (GBDT::TrainOneIter, gbdt.cpp:353).
        Returns True if training should stop (no splits possible)."""
        if self._fault_plan is not None:
            self._fault_plan.at_iteration(self.iter)
        K = self.num_tree_per_iteration
        prof = self.profiler
        if prof is not None:
            prof.iter_start()
            cp = getattr(self, "_comm_profile", None)
            if cp:
                cb = int(cp["comm_bytes_per_tree"]) * K
                prof.iter_meta(comm_mode=cp["comm_mode"], comm_bytes=cb)
                prof.add_counter("comm_bytes", cb)
        init_scores = np.zeros(K)
        with self._prof_span("boost"):
            if grad is None or hess is None:
                if self.iter == 0:
                    init_scores = self._boost_from_average()
                g_dev, h_dev = self.boost()
            else:
                grad = np.asarray(grad, np.float32).reshape(K, -1)
                hess = np.asarray(hess, np.float32).reshape(K, -1)
                if self._host_pad != self.num_data:
                    pad = ((0, 0), (0, self._host_pad - self.num_data))
                    grad = np.pad(grad, pad)
                    hess = np.pad(hess, pad)
                g_dev = self._put_rows(jnp.asarray(grad), row_axis=1)
                h_dev = self._put_rows(jnp.asarray(hess), row_axis=1)
        self._count_dispatch()   # gradient computation

        strat = self.sample_strategy
        if self._in_bag_dev is None or strat.resamples_at(self.iter):
          with self._prof_span("bagging"):
            if strat.needs_grad:
                g_arg = g_dev[:, :self.num_data]
                h_arg = h_dev[:, :self.num_data]
            else:
                g_arg = h_arg = None
            in_bag = strat.sample(self.iter, g_arg, h_arg)
            if self._host_pad != self.num_data:
                padding = [(0, 0)] * (in_bag.ndim - 1) + \
                    [(0, self._host_pad - self.num_data)]
                in_bag = jnp.pad(in_bag, padding)
            self._in_bag_dev = self._put_rows(in_bag,
                                              row_axis=in_bag.ndim - 1)
        in_bag = self._in_bag_dev

        lr = jnp.float32(self.shrinkage_rate)
        feat_mask = self._feature_mask_for_iter()
        base_seed = self.config.seed or 0
        t_grow0 = (time.perf_counter()
                   if (prof is not None and self._pre_part) else None)
        for k in range(K):
            with self._prof_span("grow"):
                tree_dev, leaf_of_row, new_scores = self._grow_step(
                    self.X_t, g_dev[k], h_dev[k],
                    in_bag if in_bag.ndim == 1 else in_bag[k],
                    self.scores[k], lr, feat_mask,
                    jnp.int32((base_seed + self.iter) * K + k))
            self._count_dispatch()   # tree-grow dispatch
            if (self.objective is not None
                    and self.objective.need_renew_tree_output):
                tree_dev, new_scores = self._renew_tree_output(
                    k, tree_dev, leaf_of_row, lr)
            if self._linear:
                # per-leaf ridge fits on the host (linear_tree_learner.cpp
                # CalculateLinear); scores advance by the LINEAR outputs
                bias = float(init_scores[k]) if self.iter == 0 else 0.0
                self._fit_and_apply_linear(
                    k, tree_dev, leaf_of_row, g_dev[k], h_dev[k],
                    in_bag if in_bag.ndim == 1 else in_bag[k], bias)
                continue
            with self._prof_span("score-update"):
                self.scores = self.scores.at[k].set(new_scores)
                # valid scores update BEFORE the bias fold: scorers
                # received the init score separately in _boost_from_average
                # (the reference updates scores before AddBias,
                # gbdt.cpp:424-428). leaf_value on the DeviceTree is
                # pre-shrinkage, so lr is applied here.
                for vi in range(len(self.valid_sets)):
                    self._valid_scores[vi] = \
                        self._valid_scores[vi].at[k].set(
                            self._valid_update(
                                tree_dev.split_feature,
                                tree_dev.threshold_bin,
                                tree_dev.default_left, tree_dev.left_child,
                                tree_dev.right_child, tree_dev.num_leaves,
                                tree_dev.leaf_value,
                                self._valid_Xt[vi],
                                tuple(self._valid_meta[vi]),
                                self._valid_scores[vi][k], lr,
                                tree_dev.split_is_cat,
                                tree_dev.split_cat_bitset))
                self._count_dispatch(len(self.valid_sets))
            # boost-from-average bias is folded into the first tree at
            # materialization time (gbdt.cpp:425-427)
            bias = init_scores[k] if self.iter == 0 else 0.0
            self._pending.append((tree_dev, float(bias)))

        if t_grow0 is not None:
            self._record_grow_skew(time.perf_counter() - t_grow0)
        self.iter += 1
        if prof is not None:
            prof.iter_end(n_rows=self.num_data)
        # The stop condition requires a host readback (a full device
        # drain), so it is only REALLY evaluated at power-of-2 iterations
        # and then every _stop_check_interval; in between, training streams
        # fully asynchronously. Worst case this appends a few extra
        # constant-zero trees past exhaustion (harmless to scores: stump
        # trees carry value 0, mirroring AsConstantTree(0), gbdt.cpp:443).
        if self._stopped:
            return True
        it = self.iter
        if (it & (it - 1)) == 0 or it % self._stop_check_interval == 0:
            self._stopped = self._check_stopped()
            return self._stopped
        return False

    # ------------------------------------------------------------------
    # resilience: step watchdog + comm-mode degradation + straggler feed
    # (docs/ROBUSTNESS.md)
    def _grow_step(self, X_t, g, h, in_bag, scores_k, lr, feat_mask, seed):
        """Watchdog around the jitted tree-grow dispatch: bounded retry
        with exponential backoff for transient device/step errors, plus
        a one-way reduce_scatter -> allreduce degrade of the histogram
        exchange after repeated collective failures (re-pinned into the
        autotune cache so the next run of this shape skips the broken
        collective). Tree growth is a pure function of its inputs, so a
        retry after a transient fault cannot change the trained model."""
        if self._fault_plan is None and self.config.step_max_retries == 0:
            return self._train_tree(X_t, g, h, in_bag, scores_k, lr,
                                    feat_mask, seed, self.meta)
        attempt = 0
        while True:
            try:
                if self._fault_plan is not None:
                    self._fault_plan.maybe_fail_collective(self.iter)
                return self._train_tree(X_t, g, h, in_bag, scores_k, lr,
                                        feat_mask, seed, self.meta)
            except Exception as e:
                from ..parallel import is_collective_error
                if is_collective_error(e):
                    self._collective_failures += 1
                    log_warning(
                        f"histogram-exchange failure "
                        f"#{self._collective_failures} at iteration "
                        f"{self.iter}: {e}")
                    if self._collective_failures >= 2 \
                            and self._degrade_comm_mode(reason=repr(e)):
                        continue        # degraded exchange; retry at once
                attempt += 1
                if attempt > self.config.step_max_retries:
                    raise
                backoff = self.config.step_retry_backoff_s \
                    * (2 ** (attempt - 1))
                log_warning(
                    f"grow step failed at iteration {self.iter} (attempt "
                    f"{attempt}/{self.config.step_max_retries}): {e}; "
                    f"retrying in {backoff:.3f}s")
                if backoff > 0:
                    time.sleep(backoff)

    def _degrade_comm_mode(self, reason: str = "") -> bool:
        """reduce_scatter -> allreduce fallback: allreduce moves more
        bytes but is the simpler collective (no feature-slice ownership,
        no winner sync), so it is the safe harbor when the scatter path
        keeps failing. One-way; returns True when a degrade happened."""
        if not (self.use_dist and not self._feat_par):
            return False
        mode = str(self.grow_cfg.parallel_hist_mode)
        if mode == "auto":
            cp = getattr(self, "_comm_profile", None) or {}
            mode = str(cp.get("comm_mode", "allreduce"))
        if mode == "allreduce":
            return False
        log_warning(f"degrading histogram exchange '{mode}' -> "
                    "'allreduce' after repeated collective failures; "
                    "pinning the choice in the autotune cache")
        self.grow_cfg = self.grow_cfg._replace(
            parallel_hist_mode="allreduce")
        try:
            from ..runtime.autotune import pin_comm_decision
            self.autotune_decision = pin_comm_decision(
                n_rows=self.num_data,
                n_features=int(self.X_t.shape[0]),
                max_bin=self._max_bin,
                num_leaves=self.config.num_leaves,
                mesh_size=self.n_shards,
                mode="allreduce",
                cache_path=self.config.autotune_cache,
                reason=reason or "repeated collective failures")
        except Exception:
            pass    # a cache miss next run, never a training failure
        self._comm_profile = self._comm_iter_profile()
        if self.profiler is not None and self._comm_profile:
            self.profiler.extras["comm"] = dict(self._comm_profile)
        self._build_jit_fns()
        return True

    def _record_grow_skew(self, span_s: float) -> None:
        """Feed this rank's grow wall into the cross-rank straggler
        detector (runtime/profiler.py). Multi-host only: on a single
        host all shards share one dispatch clock, so per-rank skew is
        unobservable from here (tests feed synthetic spans instead)."""
        try:
            from jax.experimental import multihost_utils
            spans = np.asarray(multihost_utils.process_allgather(
                np.asarray([span_s], np.float64))).reshape(-1)
            self.profiler.record_rank_spans("grow", spans)
        except Exception:
            pass

    def load_init_model(self, init) -> None:
        """Continued training from an existing model (reference:
        engine.py:234-242 -> CreateBoosting(file), boosting.cpp:70-90):
        adopt the trees and replay their outputs onto the training scores.
        `init` is a GBDT instance or a model-file path/string."""
        if isinstance(init, str):
            import os
            s = open(init).read() if os.path.exists(init) else init
            init = GBDT.load_model_from_string(s, self.config)
        import copy as _copy
        trees = [_copy.deepcopy(t) for t in init.models]
        if not trees:
            return
        K = self.num_tree_per_iteration
        # the ORIGINAL binned matrix: self.X_t may hold EFB bundle columns
        Xb = self.train_set.X_binned[:self.num_data]
        add = np.zeros((K, self.num_data), np.float32)
        for i, tree in enumerate(trees):
            self._ensure_binned_traversal(tree)
            leaf = tree.get_leaf_binned(Xb, self)
            add[i % K] += np.asarray(self._tree_output(
                tree, self._raw_or_none(self.train_set), leaf), np.float32)
        if self._host_pad != self.num_data:
            add = np.pad(add, ((0, 0), (0, self._host_pad - self.num_data)))
        self.scores = self.scores + self._put_rows(jnp.asarray(add),
                                                   row_axis=1)
        self._models = trees + self._models
        self.iter = len(trees) // max(K, 1) + self.iter
        log_info(f"Continued training from {len(trees)} existing trees")

    def _ensure_binned_traversal(self, tree: Tree) -> None:
        """File-loaded trees carry real-valued thresholds; derive the
        training-time binned attributes (inner feature ids, bin
        thresholds, bin bitsets) so they can be replayed over the binned
        matrix (continued training / DART replay)."""
        if getattr(tree, "split_feature_inner", None) is not None:
            return
        real2inner = {r: i for i, r in enumerate(self.real_feature_index)}
        m = max(tree.num_leaves - 1, 0)
        inner = np.zeros(m, np.int32)
        thr_bin = np.zeros(m, np.int32)
        is_cat = np.zeros(m, bool)
        W = max((self.num_bins_padded + 31) // 32, 1)
        bits = np.zeros((m, W), np.uint32)
        for i in range(m):
            real = int(tree.split_feature[i])
            if real not in real2inner:
                log_fatal(
                    f"init_model splits on feature {real} which is unused "
                    "(trivial/constant) in the current training data; "
                    "continued training requires compatible features")
            fi = real2inner[real]
            inner[i] = fi
            mp = self.mappers[fi]
            if tree.num_cat > 0 and (int(tree.decision_type[i]) & 1):
                is_cat[i] = True
                ci = int(tree.threshold[i])   # cat splits store cat_idx
                thr_bin[i] = ci
                s0 = int(tree.cat_boundaries[ci])
                s1 = int(tree.cat_boundaries[ci + 1])
                words = np.asarray(tree.cat_threshold[s0:s1], np.uint32)
                for b in range(min(mp.num_bin, 32 * W)):
                    v = mp.bin_2_categorical[b] \
                        if b < len(mp.bin_2_categorical) else -1
                    if 0 <= v < 32 * len(words) and \
                            (words[v >> 5] >> (v & 31)) & 1:
                        bits[i, b >> 5] |= np.uint32(1 << (b & 31))
            else:
                thr_bin[i] = int(mp.value_to_bin(
                    np.asarray([tree.threshold[i]]))[0])
        tree.split_feature_inner = inner
        tree.threshold_in_bin = thr_bin
        tree.split_is_cat = is_cat
        tree.split_cat_bitset_bins = bits

    def _fit_and_apply_linear(self, k: int, tree_dev, leaf_of_row,
                              g_dev, h_dev, in_bag, bias: float) -> None:
        """Linear-tree per-iteration host path: materialize the tree,
        ridge-fit its leaves on raw branch features
        (linear_tree_learner.cpp:183-345), advance training and valid
        scores by the LINEAR outputs, and record the host tree."""
        from .linear import fit_linear_models

        nd = self.num_data
        host, lor, g, h, bag = jax.device_get(
            (tree_dev, leaf_of_row, g_dev, h_dev, in_bag))
        tree = self._device_tree_to_host(host)
        lor = np.asarray(lor)[:nd]
        g = np.asarray(g)[:nd]
        h = np.asarray(h)[:nd]
        bag = np.asarray(bag)[:nd]
        # materialize pending first so model order stays iteration-major
        self._materialize_models()
        is_first = len(self._models) < self.num_tree_per_iteration
        delta = fit_linear_models(
            tree, self._raw, lor, g, h, bag,
            linear_lambda=float(self.config.linear_lambda),
            shrinkage=self.shrinkage_rate,
            numeric_inner=self._lin_numeric,
            inner_to_real=self._lin_inner2real,
            is_first_tree=is_first)
        dd = np.asarray(delta, np.float32)
        if self._host_pad != nd:
            dd = np.pad(dd, (0, self._host_pad - nd))
        self.scores = self.scores.at[k].set(
            self.scores[k] + jnp.asarray(dd))
        for vi in range(len(self.valid_sets)):
            v_raw = self.valid_sets[vi].raw_data
            if v_raw is None:
                log_fatal("linear_tree validation requires raw data on "
                          "the valid Dataset")
            lin = np.asarray(tree.predict(v_raw), np.float32)
            self._valid_scores[vi] = self._valid_scores[vi].at[k].set(
                self._valid_scores[vi][k] + jnp.asarray(lin))
        if abs(bias) > _KEPS:
            tree.add_bias(bias)
        self._models.append(tree)

    def _renew_tree_output(self, k: int, tree_dev, leaf_of_row, lr):
        """Leaf-output renewal for l1/quantile/mape: replace each leaf's
        value with the objective's percentile of the leaf's residuals
        (reference: RenewTreeOutput, objective_function.h:58, applied at
        serial_tree_learner.cpp:928-966 BEFORE shrinkage/score update).
        Host computation: percentiles need per-leaf sorts; costs one
        device readback per iteration for these objectives."""
        alpha = self.objective.renew_tree_output_quantile()
        if alpha is None:
            return tree_dev, self.scores[k] + (
                tree_dev.leaf_value * lr)[leaf_of_row]
        N = self.num_data
        lor, s_prev, lv, nl, inb = jax.device_get(
            (leaf_of_row, self.scores[k], tree_dev.leaf_value,
             tree_dev.num_leaves, self._in_bag_dev))
        lor = np.asarray(lor)[:N]
        s_prev = np.asarray(s_prev, np.float64)[:N]
        leaf_vals = np.asarray(lv, np.float64).copy()
        inb = np.asarray(inb)
        inb = (inb[k] if inb.ndim > 1 else inb)[:N] > 0
        label = np.asarray(self.objective.label, np.float64)
        resid = label - s_prev
        w = self.objective.renew_sample_weights()
        from ..objectives import percentile_ref, weighted_percentile_ref
        for leaf in range(int(nl)):
            m = inb & (lor == leaf)
            if not m.any():
                continue
            if w is None:
                leaf_vals[leaf] = percentile_ref(resid[m], alpha)
            else:
                leaf_vals[leaf] = weighted_percentile_ref(
                    resid[m], w[:N][m], alpha)
        lv_new = jnp.asarray(leaf_vals, jnp.float32)
        tree_dev = tree_dev._replace(leaf_value=lv_new)
        new_scores = self.scores[k] + (lv_new * lr)[leaf_of_row]
        return tree_dev, new_scores

    def _boost_from_average(self) -> np.ndarray:
        """gbdt.cpp:328: initial score from the objective's average."""
        K = self.num_tree_per_iteration
        init_scores = np.zeros(K)
        if (self.objective is None or self._has_init_score
                or not self.config.boost_from_average):
            return init_scores
        for k in range(K):
            init_scores[k] = self.objective.boost_from_score(k)
            if self._pre_part:
                # the reference averages the per-rank init scores
                # (GlobalSyncUpByMean, gbdt.cpp:322-325)
                from jax.experimental import multihost_utils
                allv = np.asarray(multihost_utils.process_allgather(
                    np.asarray([init_scores[k]], np.float64)))
                init_scores[k] = float(allv.mean())
            if abs(init_scores[k]) > _KEPS:
                self.scores = self.scores.at[k].add(
                    jnp.float32(init_scores[k]))
                for vi in range(len(self._valid_scores)):
                    self._valid_scores[vi] = self._valid_scores[vi].at[k].add(
                        jnp.float32(init_scores[k]))
                log_info(f"Start training from score {init_scores[k]:.6f}")
        return init_scores

    def _feature_mask_for_iter(
            self, it: Optional[int] = None) -> Optional[jnp.ndarray]:
        frac = self.config.feature_fraction
        F = len(self.mappers)
        if frac >= 1.0:
            # shard_map needs a stable pytree: always pass an array when
            # distributed
            return jnp.ones((F,), bool) if self.use_dist else None
        used = max(1, int(round(F * frac)))
        rng = np.random.RandomState(
            self.config.feature_fraction_seed
            + (self.iter if it is None else it))
        mask = np.zeros(F, dtype=bool)
        mask[rng.choice(F, used, replace=False)] = True
        return jnp.asarray(mask)

    def rollback_one_iter(self) -> None:
        """gbdt.cpp:463: undo the last iteration."""
        if self.iter <= 0:
            return
        self._stopped = False
        # the packed/device predict caches key on (start, end, len) and
        # would collide with the pre-rollback model after retraining
        self._packed_cache = None
        self._device_tables_cache = None
        K = self.num_tree_per_iteration
        for k in range(K):
            tree = self.models.pop()
            kk = K - 1 - k
            # subtract this tree's contribution from the scores (linear
            # trees contributed their LINEAR outputs, tree.cpp:130-155)
            leaf = tree.get_leaf_binned(
                self.train_set.X_binned[:self.num_data], self)
            contrib = np.asarray(self._tree_output(tree, self._raw_or_none(
                self.train_set), leaf), np.float32)
            if self._host_pad != self.num_data:
                contrib = np.pad(contrib,
                                 (0, self._host_pad - self.num_data))
            self.scores = self.scores.at[kk].add(
                -self._put_rows(jnp.asarray(contrib)))
            for vi, ds in enumerate(self.valid_sets):
                leaf_v = tree.get_leaf_binned(ds.X_binned, self)
                self._valid_scores[vi] = self._valid_scores[vi].at[kk].add(
                    -jnp.asarray(self._tree_output(
                        tree, self._raw_or_none(ds), leaf_v),
                        dtype=jnp.float32))
        self.iter -= 1

    @staticmethod
    def _raw_or_none(ds):
        return getattr(ds, "raw_data", None)

    def _tree_output(self, tree: Tree, raw, leaf: np.ndarray) -> np.ndarray:
        """Per-row score contribution of `tree` for precomputed leaf
        indices: constant leaf values, or the linear outputs for linear
        trees (requires the dataset's raw values)."""
        if not getattr(tree, "is_linear", False):
            return tree.leaf_value[leaf]
        if raw is None:
            log_fatal("replaying a linear tree onto scores requires the "
                      "dataset's raw feature values")
        from .linear import linear_output_for_leaves
        return linear_output_for_leaves(tree, np.asarray(raw), leaf)

    # ------------------------------------------------------------------
    def _device_tree_to_host(self, host: Any) -> Tree:
        """Convert pulled DeviceTree arrays into a host Tree with real
        thresholds and real feature indices. Categorical splits translate
        the device bin-bitset into the reference's category-value bitsets
        (cat_boundaries/cat_threshold; split_info.hpp cat_threshold,
        tree.cpp Tree::Split categorical path)."""
        n = int(host.num_leaves)
        m = max(n - 1, 0)
        sf_inner = np.asarray(host.split_feature[:m], np.int32)
        thr_bin = np.array(host.threshold_bin[:m], np.int32)  # writable copy
        dleft = np.asarray(host.default_left[:m], bool)
        is_cat = np.asarray(host.split_is_cat[:m], bool)
        cat_bits_bins = np.asarray(host.split_cat_bitset[:m], np.uint32)
        thr_real = np.zeros(m, dtype=np.float64)
        dtype_arr = np.zeros(m, dtype=np.int8)
        num_cat = 0
        cat_boundaries = [0]
        cat_threshold: List[int] = []
        for i in range(m):
            mp = self.mappers[sf_inner[i]]
            if is_cat[i]:
                # bins in the left set -> raw category values -> value bitset
                bits = cat_bits_bins[i]
                sel_bins = [b for b in range(min(mp.num_bin, 32 * len(bits)))
                            if (bits[b >> 5] >> (b & 31)) & 1]
                cats = [mp.bin_2_categorical[b] for b in sel_bins]
                max_cat = max(cats) if cats else 0
                nwords = max_cat // 32 + 1
                words = np.zeros(nwords, dtype=np.uint32)
                for v in cats:
                    words[v // 32] |= np.uint32(1 << (v % 32))
                thr_real[i] = num_cat          # threshold stores cat_idx
                thr_bin[i] = num_cat
                cat_boundaries.append(cat_boundaries[-1] + nwords)
                cat_threshold.extend(words.tolist())
                num_cat += 1
                dtype_arr[i] = make_decision_type(True, False,
                                                  mp.missing_type)
            else:
                thr_real[i] = mp.bin_to_value(int(thr_bin[i]))
                dtype_arr[i] = make_decision_type(False, bool(dleft[i]),
                                                  mp.missing_type)
        real_feat = np.asarray(
            [self.real_feature_index[f] for f in sf_inner], np.int32)
        lr = self.shrinkage_rate
        t = Tree.from_arrays(
            num_leaves=n,
            split_feature=real_feat,
            threshold_bin=thr_bin,
            threshold_real=thr_real,
            decision_type=dtype_arr,
            left_child=np.asarray(host.left_child[:m], np.int32),
            right_child=np.asarray(host.right_child[:m], np.int32),
            split_gain=np.asarray(host.split_gain[:m], np.float32),
            leaf_value=np.asarray(host.leaf_value[:n], np.float64) * lr,
            leaf_weight=np.asarray(host.leaf_weight[:n], np.float64),
            leaf_count=np.asarray(host.leaf_count[:n], np.int64),
            internal_value=np.asarray(host.internal_value[:m], np.float64) * lr,
            internal_weight=np.asarray(host.internal_weight[:m], np.float64),
            internal_count=np.asarray(host.internal_count[:m], np.int64),
            shrinkage=lr,
            cat_boundaries=np.asarray(cat_boundaries, np.int32),
            cat_threshold=np.asarray(cat_threshold, np.uint32),
            num_cat=num_cat,
        )
        t.split_feature_inner = sf_inner  # kept for binned traversal
        t.split_is_cat = is_cat
        t.split_cat_bitset_bins = cat_bits_bins
        return t

    # ------------------------------------------------------------------
    # evaluation
    # ------------------------------------------------------------------
    def get_eval_result(self, metrics_per_set: Dict[str, Sequence[Metric]]
                        ) -> List[Tuple[str, str, float, bool]]:
        """[(dataset_name, metric_name, value, is_higher_better)]"""
        out = []
        for name, metrics in metrics_per_set.items():
            if name == "training":
                if self._pre_part:
                    # each process evaluates its OWN row shard (metrics
                    # were initialized with the local metadata); the
                    # reference syncs rank sums for exact global metrics
                    # (GlobalSum in binary_metric.hpp) — local-shard
                    # values here, noted in the launcher docs
                    score = np.stack([
                        self._local_scores(k)
                        for k in range(self.num_tree_per_iteration)])
                else:
                    score = np.asarray(
                        jax.device_get(self.scores))[:, :self.num_data]
            else:
                vi = self.valid_names.index(name)
                score = np.asarray(jax.device_get(self._valid_scores[vi]))
            s = score if score.shape[0] > 1 else score[0]
            for metric in metrics:
                for mn, val, hib in metric.eval(s, self.objective):
                    out.append((name, mn, val, hib))
        if self._pre_part and out:
            # every rank must see IDENTICAL metric values or metric-driven
            # callbacks (early_stopping) diverge and deadlock the process
            # group: sync by averaging the per-rank shard values (the
            # reference syncs exact sums, GlobalSum in binary_metric.hpp;
            # the mean of shard metrics is deterministic and
            # rank-identical, which is the property that matters here)
            from jax.experimental import multihost_utils
            vals = np.asarray([v for (_, _, v, _) in out], np.float64)
            allv = np.asarray(multihost_utils.process_allgather(vals))
            mean = allv.mean(axis=0)
            out = [(n_, m_, float(mean[i]), h_)
                   for i, (n_, m_, _, h_) in enumerate(out)]
        return out

    # ------------------------------------------------------------------
    # prediction (host trees; raw features)
    # ------------------------------------------------------------------
    def _packed_model(self, start_iteration: int, end: int):
        """Cached PackedModel for the [start_iteration, end) tree slice
        (the single/batch fast-path init, c_api.h:1399 FastInit analog)."""
        key = (start_iteration, end, len(self.models))
        cached = getattr(self, "_packed_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        from .predictor import PackedModel
        K = self.num_tree_per_iteration
        pm = PackedModel(self.models[start_iteration * K:end * K], K)
        self._packed_cache = (key, pm)
        return pm

    def predict_raw(self, X: np.ndarray, start_iteration: int = 0,
                    num_iteration: int = -1,
                    pred_early_stop: bool = False,
                    pred_early_stop_freq: int = 10,
                    pred_early_stop_margin: float = 10.0) -> np.ndarray:
        # f32 inputs may route to the device predictor below — capture
        # the original dtype before the host paths' f64 upcast
        with span("predict/raw"):
            return self._predict_raw(X, start_iteration, num_iteration,
                                     pred_early_stop, pred_early_stop_freq,
                                     pred_early_stop_margin)

    def _predict_raw(self, X, start_iteration, num_iteration,
                     pred_early_stop, pred_early_stop_freq,
                     pred_early_stop_margin) -> np.ndarray:
        K = self.num_tree_per_iteration
        total_iters = len(self.models) // K
        end = total_iters if num_iteration <= 0 else min(
            total_iters, start_iteration + num_iteration)
        rows = np.shape(X)[0]
        if end <= start_iteration:
            return np.zeros((K, rows), dtype=np.float64)
        # large FLOAT32 batches score on the accelerator (the matmul
        # predictor, models/predictor.py predict_margin_device — the
        # reference's parallel Predictor analog, application/predictor.hpp).
        # f32-only: the device compares in f32 with floored thresholds,
        # which routes f32 values exactly like the host's f64 walk; f64
        # inputs with sub-f32 precision stay on the host. Small batches
        # and early-stop stay on the host walk too.
        # Float32 rows go to the device as they are: the float64 copy is
        # made below, for the host walk alone.
        if (getattr(X, "dtype", None) == np.float32 and rows >= 100_000
                and not pred_early_stop
                and not any(getattr(t, "is_linear", False)
                            for t in self.models)):
            if jax.default_backend() == "tpu":
                from .predictor import (build_device_tables,
                                        predict_margin_device)
                trees = self.models[start_iteration * K:end * K]
                key = (start_iteration, end, len(self.models))
                cache = getattr(self, "_device_tables_cache", None)
                if cache is None or cache[0] != key:
                    # None where the tables do not fit beside the row
                    # blocks the predictor has in flight
                    with span("predict/tables"):
                        cache = (key, build_device_tables(
                            trees, K, X.shape[1], rows=rows))
                tables = cache[1]
                if tables is None or tables.over_budget(rows):
                    # row blocks and tables do not fit the device
                    # together: the host walk below answers, and says so
                    span_count(tables_over_budget=1)
                else:
                    self._device_tables_cache = cache
                    span_count(trees=len(trees), device_route=1)
                    out = predict_margin_device(trees, K, X, tables=tables)
                    if self.average_output and end > start_iteration:
                        out /= (end - start_iteration)
                    return out
        with span("predict/cast_f64"):
            X = np.asarray(X, dtype=np.float64)
        span_count(trees=(end - start_iteration) * K, device_route=0)
        with span("predict/host_walk"):
            pm = self._packed_model(start_iteration, end)
            # early stop is margin-based and meaningless for averaged
            # (RF) output (prediction_early_stop.cpp operates on boosted
            # margins)
            margin = (pred_early_stop_margin
                      if pred_early_stop and not self.average_output
                      else None)
            # freq counts ITERATIONS (each covering all K class trees),
            # as in the reference's per-iteration early-stop counter
            out = pm.predict_margin(X, early_stop_margin=margin,
                                    early_stop_freq=max(
                                        1, int(pred_early_stop_freq)))
        if self.average_output and end > start_iteration:
            out /= (end - start_iteration)
        return out

    def predict_single_row(self, x: np.ndarray, start_iteration: int = 0,
                           num_iteration: int = -1) -> np.ndarray:
        """One-row fast path over the cached packed trees ([K] margins;
        LGBM_BoosterPredictForMatSingleRowFast semantics)."""
        K = self.num_tree_per_iteration
        total_iters = len(self.models) // K
        end = total_iters if num_iteration <= 0 else min(
            total_iters, start_iteration + num_iteration)
        if end <= start_iteration:
            return np.zeros(K, np.float64)
        pm = self._packed_model(start_iteration, end)
        out = pm.predict_single(np.asarray(x, np.float64))
        if self.average_output:
            out /= (end - start_iteration)
        return out

    def predict(self, X: np.ndarray, raw_score: bool = False,
                start_iteration: int = 0, num_iteration: int = -1,
                **pred_kwargs) -> np.ndarray:
        raw = self.predict_raw(X, start_iteration, num_iteration,
                               **pred_kwargs)
        with span("predict/convert_output"):
            if not raw_score and self.objective is not None \
                    and self.objective.need_convert_output:
                raw = self.objective.convert_output(raw)
            return raw[0] if raw.shape[0] == 1 else raw.T

    def predict_leaf_index(self, X: np.ndarray, start_iteration: int = 0,
                           num_iteration: int = -1) -> np.ndarray:
        X = np.asarray(X, dtype=np.float64)
        K = self.num_tree_per_iteration
        total_iters = len(self.models) // K
        end = total_iters if num_iteration <= 0 else min(
            total_iters, start_iteration + num_iteration)
        cols = []
        for it in range(start_iteration, end):
            for k in range(K):
                cols.append(self.models[it * K + k].get_leaf_index(X))
        return np.stack(cols, axis=1) if cols else np.zeros((X.shape[0], 0))

    # ------------------------------------------------------------------
    # model serialization (gbdt_model_text.cpp)
    # ------------------------------------------------------------------
    def save_model_to_string(self, start_iteration: int = 0,
                             num_iteration: int = -1,
                             importance_type: int = 0) -> str:
        K = self.num_tree_per_iteration
        total_iters = len(self.models) // K if K else 0
        start_iteration = max(0, min(start_iteration, total_iters))
        if num_iteration > 0:
            num_used = min((start_iteration + num_iteration) * K,
                           len(self.models))
        else:
            num_used = len(self.models)
        start_model = start_iteration * K

        lines = ["tree"]
        lines.append(f"version={MODEL_VERSION}")
        lines.append(f"num_class={self.num_class}")
        lines.append(f"num_tree_per_iteration={K}")
        lines.append(f"label_index={self.label_idx_}")
        lines.append(f"max_feature_idx={self.max_feature_idx_}")
        if self.objective is not None:
            lines.append(f"objective={self.objective.to_string()}")
        if self.average_output:
            lines.append("average_output")
        lines.append("feature_names=" + " ".join(self.feature_names_))
        lines.append("feature_infos=" + " ".join(self.feature_infos_))

        tree_strs = []
        for i in range(start_model, num_used):
            s = f"Tree={i - start_model}\n" + self.models[i].to_string() + "\n"
            tree_strs.append(s)
        lines.append("tree_sizes=" + " ".join(str(len(s)) for s in tree_strs))
        lines.append("")
        body = "\n".join(lines) + "\n"
        body += "".join(tree_strs)
        body += "end of trees\n"

        imp = self.feature_importance(importance_type, num_iteration)
        pairs = [(int(v), self.feature_names_[i]) for i, v in enumerate(imp)
                 if v > 0]
        pairs.sort(key=lambda p: -p[0])
        body += "\nfeature_importances:\n"
        for v, name in pairs:
            body += f"{name}={v}\n"
        body += "\nparameters:\n" + (self.loaded_parameter
                                     or self.config.to_string()) + "\n"
        body += "end of parameters\n"
        return body

    def feature_importance(self, importance_type: int = 0,
                           num_iteration: int = -1) -> np.ndarray:
        """reference: GBDT::FeatureImportance (gbdt.cpp)."""
        K = self.num_tree_per_iteration
        end = len(self.models) if num_iteration <= 0 else min(
            len(self.models), num_iteration * K)
        imp = np.zeros(self.max_feature_idx_ + 1, dtype=np.float64)
        for tree in self.models[:end]:
            m = tree.num_leaves - 1
            for i in range(m):
                if tree.split_gain[i] > 0:
                    if importance_type == 0:
                        imp[tree.split_feature[i]] += 1.0
                    else:
                        imp[tree.split_feature[i]] += tree.split_gain[i]
        return imp

    @classmethod
    def load_model_from_string(cls, model_str: str,
                               config: Optional[Config] = None) -> "GBDT":
        """reference: GBDT::LoadModelFromString (gbdt_model_text.cpp:590)."""
        start_backend()
        with span("booster/load", bytes=len(model_str)):
            gbdt = cls._load_model_from_string(model_str, config)
            span_count(trees=len(gbdt.models))
        return gbdt

    @classmethod
    def _load_model_from_string(cls, model_str: str,
                                config: Optional[Config]) -> "GBDT":
        from ..config import resolve_params
        config = config or Config()
        gbdt = cls(config, None, None)
        lines = model_str.split("\n")
        header: Dict[str, str] = {}
        i = 0
        while i < len(lines):
            line = lines[i].strip()
            if line.startswith("Tree="):
                break
            if "=" in line:
                k, v = line.split("=", 1)
                header[k] = v
            elif line == "average_output":
                gbdt.average_output = True
            i += 1
        gbdt.num_class = int(header.get("num_class", "1"))
        gbdt.num_tree_per_iteration = int(
            header.get("num_tree_per_iteration", "1"))
        gbdt.label_idx_ = int(header.get("label_index", "0"))
        gbdt.max_feature_idx_ = int(header.get("max_feature_idx", "0"))
        gbdt.feature_names_ = header.get("feature_names", "").split()
        gbdt.feature_infos_ = header.get("feature_infos", "").split()
        if "objective" in header:
            obj_str = header["objective"]
            cfg2 = _config_from_objective_string(obj_str, config)
            from ..objectives import create_objective
            gbdt.objective = create_objective(cfg2)
            gbdt.config = cfg2
            gbdt.num_tree_per_iteration = max(
                gbdt.num_tree_per_iteration,
                gbdt.objective.num_model_per_iteration
                if gbdt.objective else 1)
        # parse trees
        blocks = model_str.split("Tree=")
        for blk in blocks[1:]:
            body = blk.split("\n\n")[0]
            if "end of trees" in body:
                body = body.split("end of trees")[0]
            gbdt.models.append(Tree.from_string(body))
        gbdt.iter = len(gbdt.models) // max(gbdt.num_tree_per_iteration, 1)
        return gbdt

    def _wave_route(self) -> Optional[str]:
        """How the wave grower moves rows on this table
        (ops/grow_wave.py wave_route), None for the other growers."""
        if self.grower not in ("wave", "wave_exact"):
            return None
        from ..ops.grow_wave import wave_route
        return wave_route(self.X_t, self.grow_cfg)

    def _wave_decide_trees(self, n: int) -> int:
        """Of a chunk of `n` iterations, the trees whose waves decide
        each row inside the kernel (the `wave_decide_trees` counter)."""
        if self._wave_route() != "decide":
            return 0
        return n * self.num_tree_per_iteration


def _gradients(obj, scores, label, weight, ostate):
    """``obj.get_gradients`` with the objective's device state, where it
    has one, handed in from the caller's arguments."""
    if ostate is None:
        return obj.get_gradients(scores, label, weight)
    return obj.get_gradients(scores, label, weight, ostate)


class _AsyncTreeDrain:
    """Background materializer for batched-training chunk records.

    train_iters_batched submits one stacked record per chunk; the worker
    thread device_get's it and converts it to host Trees while the main
    thread dispatches the NEXT chunk — double-buffering host
    materialization against device compute. Converted trees are folded
    into ``gbdt._models`` only on flush() (main thread), so the model
    list is never mutated concurrently. While a drain is attached,
    nothing else appends to ``gbdt._pending``."""

    def __init__(self, gbdt: "GBDT"):
        self._gbdt = gbdt
        self._q: "queue.Queue" = queue.Queue()
        self._done: List[List[Tree]] = []
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, name="gbdt-tree-drain", daemon=True)
        self._thread.start()

    def submit(self, record) -> None:
        self._q.put(record)

    def idle(self) -> bool:
        """Nothing queued, converting, or converted and not yet folded."""
        return (self._q.unfinished_tasks == 0 and not self._done
                and self._error is None)

    def _run(self) -> None:
        while True:
            rec = self._q.get()
            try:
                if rec is None:
                    return
                if self._error is not None:
                    continue   # fail fast: skip work after first error
                host = jax.device_get(rec[0])
                self._done.append(
                    self._gbdt._host_record_to_trees(host, rec[1]))
            except BaseException as e:   # surfaced on flush()
                self._error = e
            finally:
                self._q.task_done()

    def flush(self) -> None:
        """Block until the queue drains, then fold converted trees into
        the owning GBDT's _models (in submission order). Re-raises any
        worker-side error on the caller's thread."""
        self._q.join()
        if self._error is not None:
            err, self._error = self._error, None
            raise err
        done, self._done = self._done, []
        for trees in done:
            self._gbdt._models.extend(trees)

    def close(self) -> None:
        self.flush()
        self._q.put(None)
        self._thread.join(timeout=10.0)


def _config_from_objective_string(obj_str: str, base: Config) -> Config:
    """Parse 'binary sigmoid:1' style objective strings from model files."""
    import dataclasses
    parts = obj_str.split()
    cfg = dataclasses.replace(base, objective=parts[0])
    for tok in parts[1:]:
        if ":" in tok:
            k, v = tok.split(":", 1)
            if k == "num_class":
                cfg = dataclasses.replace(cfg, num_class=int(v))
            elif k == "sigmoid":
                cfg = dataclasses.replace(cfg, sigmoid=float(v))
    return cfg
