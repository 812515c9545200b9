"""Binned dataset construction.

TPU-native analog of the reference Dataset/DatasetLoader/Metadata
(include/LightGBM/dataset.h:49-1086, src/io/dataset.cpp,
src/io/dataset_loader.cpp): sample rows -> per-feature BinMapper -> dense
binned feature matrix.

TPU-first layout decision: instead of per-feature Bin objects (dense_bin.hpp /
sparse_bin.hpp) the binned matrix is ONE dense [num_data, num_features] uint8
(or uint16 when any feature has >256 bins) array pushed to HBM, padded so XLA
sees static, tile-aligned shapes. Histogram/partition kernels consume it
directly (ops/histogram.py). Sparse/EFB bundling collapses into this same
dense layout (features are already "bundled" into one matrix).
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from ..config import Config
from ..runtime.profiler import count as span_count, span
from ..utils.log import log_fatal, log_info, log_warning
from .binning import (BIN_TYPE_CATEGORICAL, BIN_TYPE_NUMERICAL, BinMapper)


class Metadata:
    """Labels, weights, query boundaries, init scores
    (reference: include/LightGBM/dataset.h:49-134, src/io/metadata.cpp)."""

    def __init__(self, num_data: int):
        self.num_data = num_data
        self.label: Optional[np.ndarray] = None
        self.weight: Optional[np.ndarray] = None
        self.query_boundaries: Optional[np.ndarray] = None  # [num_queries+1]
        self.init_score: Optional[np.ndarray] = None

    def set_label(self, label: Optional[np.ndarray]) -> None:
        if label is None:
            self.label = None
            return
        label = np.asarray(label, dtype=np.float32).reshape(-1)
        if len(label) != self.num_data:
            log_fatal(f"Length of label ({len(label)}) differs from "
                      f"num_data ({self.num_data})")
        self.label = label

    def set_weight(self, weight: Optional[np.ndarray]) -> None:
        if weight is None:
            self.weight = None
            return
        weight = np.asarray(weight, dtype=np.float32).reshape(-1)
        if len(weight) != self.num_data:
            log_fatal(f"Length of weight ({len(weight)}) differs from "
                      f"num_data ({self.num_data})")
        if np.any(weight < 0):
            log_fatal("Weights should be non-negative")
        self.weight = weight

    def set_group(self, group: Optional[np.ndarray]) -> None:
        """`group` is per-query sizes (reference: Metadata::SetQuery)."""
        if group is None:
            self.query_boundaries = None
            return
        group = np.asarray(group, dtype=np.int64).reshape(-1)
        bounds = np.concatenate([[0], np.cumsum(group)])
        if bounds[-1] != self.num_data:
            log_fatal(f"Sum of query counts ({bounds[-1]}) differs from "
                      f"num_data ({self.num_data})")
        self.query_boundaries = bounds.astype(np.int32)

    def set_init_score(self, init_score: Optional[np.ndarray]) -> None:
        if init_score is None:
            self.init_score = None
            return
        init_score = np.asarray(init_score, dtype=np.float64)
        if init_score.ndim == 1 and len(init_score) % self.num_data != 0:
            log_fatal("init_score length is not a multiple of num_data")
        self.init_score = init_score

    @property
    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1


class BinnedDataset:
    """The constructed (binned) dataset
    (reference: Dataset, include/LightGBM/dataset.h:492).

    Attributes
    ----------
    X_binned : np.ndarray [num_data, num_features] uint8|uint16
        Bin index per (row, inner feature).
    mappers : list[BinMapper], one per *inner* (non-trivial) feature.
    real_feature_index : inner feature -> original column index.
    used_feature_map : original column -> inner feature index or -1.
    """

    def __init__(self) -> None:
        self.num_data: int = 0
        self.num_total_features: int = 0
        self.X_binned: Optional[np.ndarray] = None
        self.mappers: List[BinMapper] = []
        self.real_feature_index: List[int] = []
        self.used_feature_map: List[int] = []
        self.feature_names: List[str] = []
        self.metadata: Optional[Metadata] = None
        self.max_bin: int = 255
        self.reference: Optional["BinnedDataset"] = None
        # where the value->bin push of X_binned ran: "device" = the
        # packed bin table (ops/bucketize.py), "host" = the BinMapper loop
        self.binned_on: str = "host"
        # EFB (Exclusive Feature Bundling, dataset.cpp:112 FindGroups /
        # :251 FastFeatureBundling): sparse features whose non-default
        # rows never (max_conflict_rate=0) or rarely overlap share one
        # uint8 column. None = no bundling applied.
        self.bundles: Optional[List[List[int]]] = None
        self.X_bundled: Optional[np.ndarray] = None   # [N, F_b] uint8
        self.bundle_col: Optional[List[int]] = None   # inner f -> column
        self.bundle_off: Optional[List[int]] = None   # inner f -> offset,
        #                                               -1 = raw singleton
        # raw feature values, retained only when config.linear_tree needs
        # them at fit time (reference keeps Dataset raw_data the same way,
        # linear_tree_learner.cpp raw_index)
        self.raw_data: Optional[np.ndarray] = None
        # bin-width tier permutation (docs/PERF.md): tier_perm[new_inner]
        # = pre-sort inner index. Inner features are stably reordered by
        # histogram lane-width class (<=32/<=64/<=128/<=256 bins) at
        # construction so same-width features are contiguous and
        # ops/histogram_tiered.py can size one kernel per class. None =
        # reorder not applied (old binary caches before re-load).
        self.tier_perm: Optional[List[int]] = None
        # row-wise multi-value pack (MultiValDenseBin analog,
        # multi_val_dense_bin.hpp:21; docs/PERF.md): every used storage
        # column's bins as ONE row-major dense [N, F_packed] uint8 array
        # plus per-column offset/width tables into the flat per-feature-
        # offset histogram buffer (ops/histogram_rowwise.py). Built
        # lazily by `build_multival()`; derived deterministically from
        # the storage matrix, so binary-cache round-trips rebuild it
        # rather than store a second copy.
        self.X_multival: Optional[np.ndarray] = None   # [N, F_packed]
        self.multival_offsets: Optional[List[int]] = None
        self.multival_widths: Optional[List[int]] = None
        self.multival_total: int = 0
        # 4-bit packed storage (histogram_impl="rowwise_packed",
        # ops/histogram_rowwise.py Pack4Plan): two <=16-bin storage
        # columns per byte (lo nibble = earlier column), wider columns
        # in an unpacked remainder. Built lazily by
        # `build_multival_packed()`; numpy twin of the device `pack4`.
        self.X_mv_packed: Optional[np.ndarray] = None  # [N, n_bytes]
        self.X_mv_rest: Optional[np.ndarray] = None    # [N, n_rest]
        self.mv_pack_pos: Optional[List[int]] = None   # [F] nibble or -1
        self.mv_rest_pos: Optional[List[int]] = None   # [F] rest row or -1

    # -- derived per-feature arrays consumed by device kernels
    @property
    def num_features(self) -> int:
        return len(self.mappers)

    def feature_num_bins(self) -> np.ndarray:
        return np.array([m.num_bin for m in self.mappers], dtype=np.int32)

    def feature_missing_types(self) -> np.ndarray:
        return np.array([m.missing_type for m in self.mappers], dtype=np.int32)

    def feature_default_bins(self) -> np.ndarray:
        return np.array([m.default_bin for m in self.mappers], dtype=np.int32)

    def feature_is_categorical(self) -> np.ndarray:
        return np.array([m.bin_type == BIN_TYPE_CATEGORICAL
                         for m in self.mappers], dtype=bool)

    def feature_infos(self) -> List[str]:
        infos = []
        for orig in range(self.num_total_features):
            inner = self.used_feature_map[orig]
            infos.append("none" if inner < 0 else self.mappers[inner].feature_info())
        return infos

    def schema_signature(self) -> str:
        """Stable digest of the binning schema — column count, feature
        names and every mapper's bin layout (feature_infos encodes the
        bin upper bounds). The online loop's bin-compat guard compares
        this across checkpoints and resumed runs: data produced under a
        different schema must be rejected, never silently re-binned
        (docs/ONLINE.md)."""
        import hashlib
        h = hashlib.sha256()
        h.update(f"{self.num_total_features}|{self.max_bin}".encode())
        for name, info in zip(self.feature_names, self.feature_infos()):
            h.update(f"|{name}:{info}".encode())
        return h.hexdigest()

    def storage_num_bins(self) -> List[int]:
        """Per-STORAGE-COLUMN bin counts in storage order: EFB bundle
        columns count their packed width (1 shared default bin + each
        member's non-default bins), raw columns the mapper width — the
        same tuple models/gbdt.py ships as GrowConfig.hist_tiers."""
        if self.bundles is not None:
            return [int(self.mappers[members[0]].num_bin)
                    if len(members) == 1
                    else 1 + sum(int(self.mappers[f].num_bin) - 1
                                 for f in members)
                    for members in self.bundles]
        return [int(m.num_bin) for m in self.mappers]

    def build_multival(self) -> Optional[np.ndarray]:
        """Build (once) and return the row-wise multi-value pack: the
        used storage columns — EFB bundle columns when bundling is
        active, else the inner-feature columns — as one row-major
        [N, F_packed] uint8 array, with `multival_offsets`/
        `multival_widths` locating each column's bins in the flat
        row-wise histogram buffer. Returns None when the storage is not
        8-bit (the Pallas row-wise path only runs on uint8 bins).

        The pack aliases the storage matrix when it is already C-order
        (it always is for the in-memory constructors), so this costs
        only the offset tables."""
        if self.X_multival is not None:
            return self.X_multival
        X = self.X_bundled if self.bundles is not None else self.X_binned
        if X is None or X.dtype != np.uint8:
            return None
        layout = _multival_layout(self.storage_num_bins())
        if layout is None:
            return None
        self.multival_offsets, self.multival_widths, \
            self.multival_total = layout
        self.X_multival = np.ascontiguousarray(X)
        return self.X_multival

    def build_multival_packed(self):
        """Build (once) the 4-bit packed twin of the multi-value pack:
        (packed [N, n_bytes] uint8, rest [N, n_rest] uint8,
        pack_pos, rest_pos) per `ops/histogram_rowwise.py:Pack4Plan` —
        packed HOST-SIDE at load time so repeat training streams the
        halved operand without an on-device repack per histogram call.
        Returns None when the storage is not 8-bit, the layout has no
        row-wise plan, or fewer than two columns fit a nibble (packing
        then saves nothing; the plain rowwise path is strictly better)."""
        if self.X_mv_packed is not None:
            return (self.X_mv_packed, self.X_mv_rest,
                    self.mv_pack_pos, self.mv_rest_pos)
        if self.build_multival() is None:
            return None
        out = _pack4(self.X_multival, self.storage_num_bins())
        if out is None:
            return None
        self.X_mv_packed, self.X_mv_rest, \
            self.mv_pack_pos, self.mv_rest_pos = out
        return out

    @property
    def label(self) -> Optional[np.ndarray]:
        return self.metadata.label if self.metadata else None


def _init_ds(num_data: int, num_cols: int, config: Config,
             feature_names: Optional[Sequence[str]]) -> BinnedDataset:
    ds = BinnedDataset()
    ds.num_data = int(num_data)
    ds.num_total_features = int(num_cols)
    ds.max_bin = config.max_bin
    ds.feature_names = (list(feature_names) if feature_names is not None
                        else [f"Column_{i}" for i in range(num_cols)])
    return ds


def _lane_width(num_bin: int) -> int:
    """Histogram kernel lane-width class for a feature (numpy-level twin
    of ops/histogram_tiered.lane_width — duplicated so data loading never
    imports jax). >256 bins means uint16 storage, which the Pallas path
    rejects anyway; those features form their own trailing class."""
    for w in (32, 64, 128, 256):
        if num_bin <= w:
            return w
    return 512


def _multival_layout(num_bins_seq):
    """Flat row-wise histogram layout for the multi-value pack: numpy-
    level twin of `ops/histogram_rowwise.build_rowwise_plan` (offsets/
    widths/total only — duplicated so data loading never imports jax;
    tests pin the two equal). Per-column widths are the bin count
    rounded up to the 8-sublane tile, packed into 128-aligned column
    chunks of <= 2048. Returns None when any column exceeds 256 bins
    (uint16 storage has no Pallas path)."""
    offsets, widths = [], []
    col0 = used = 0
    for nb in num_bins_seq:
        if int(nb) > 256:
            return None
        w = max(-(-int(nb) // 8) * 8, 8)
        if used and used + w > 2048:
            col0 += -(-used // 128) * 128
            used = 0
        offsets.append(col0 + used)
        widths.append(w)
        used += w
    total = col0 + (-(-used // 128) * 128 if used else 0)
    return offsets, widths, total


def _pack4(X_multival, num_bins_seq):
    """4-bit storage pack: numpy-level twin of
    `ops/histogram_rowwise.py:build_pack4_plan` + `pack4` (duplicated so
    data loading never imports jax; tests pin the two equal). Columns
    with <= 16 bins get consecutive nibbles in storage order — byte
    ``pos // 2``, lo nibble when ``pos`` is even — and wider columns
    land in the unpacked remainder. Returns (packed [N, n_bytes] uint8,
    rest [N, n_rest] uint8, pack_pos, rest_pos), or None when fewer
    than two columns are packable."""
    pack_pos, rest_pos = [], []
    np_c, nr = 0, 0
    for nb in num_bins_seq:
        if int(nb) <= 16:
            pack_pos.append(np_c)
            rest_pos.append(-1)
            np_c += 1
        else:
            pack_pos.append(-1)
            rest_pos.append(nr)
            nr += 1
    if np_c < 2:
        return None
    lo_f = [f for f, p in enumerate(pack_pos) if p >= 0 and p % 2 == 0]
    hi_f = [f for f, p in enumerate(pack_pos) if p >= 0 and p % 2 == 1]
    rest_f = [f for f, r in enumerate(rest_pos) if r >= 0]
    N = X_multival.shape[0]
    lo = X_multival[:, lo_f].astype(np.uint8) & 15
    hi = X_multival[:, hi_f].astype(np.uint8) & 15
    if lo.shape[1] > hi.shape[1]:        # odd count: hi nibble stays 0
        hi = np.pad(hi, ((0, 0), (0, lo.shape[1] - hi.shape[1])))
    packed = np.ascontiguousarray(lo | (hi << 4))
    rest = (np.ascontiguousarray(X_multival[:, rest_f]) if rest_f
            else np.zeros((N, 1), np.uint8))  # dummy row keeps specs legal
    return packed, rest, pack_pos, rest_pos


def _apply_tier_order(ds: BinnedDataset,
                      reorder_binned: bool = False) -> None:
    """Stably reorder inner features by lane-width class (docs/PERF.md)
    and record the permutation in `ds.tier_perm`.

    Runs BEFORE the binning loop in the normal constructors (columns are
    then binned directly into tier order via `real_feature_index`), so
    only the three mapping tables move; `reorder_binned=True` (binary
    cache load) additionally permutes the already-binned columns. All
    consumers address features through `used_feature_map` /
    `real_feature_index`, so the reorder is invisible outside histogram
    kernel-launch grouping — except that equal-gain split ties, which
    resolve by lowest inner index, can pick a different (equally valid)
    feature on mixed-width datasets."""
    F = len(ds.mappers)
    perm = sorted(range(F),
                  key=lambda f: _lane_width(ds.mappers[f].num_bin))
    ds.tier_perm = perm
    if perm == list(range(F)):
        return
    ds.mappers = [ds.mappers[p] for p in perm]
    ds.real_feature_index = [ds.real_feature_index[p] for p in perm]
    for new_inner, orig in enumerate(ds.real_feature_index):
        ds.used_feature_map[orig] = new_inner
    if reorder_binned and ds.X_binned is not None \
            and ds.X_binned.shape[1] == F:
        ds.X_binned = np.ascontiguousarray(ds.X_binned[:, perm])


def _fit_or_adopt_mappers(ds: BinnedDataset, config: Config,
                          reference: Optional[BinnedDataset],
                          sample_col, n_sample: int,
                          categorical_feature: Sequence[int]) -> None:
    """Bin-mapper construction shared by every constructor: adopt the
    reference's mappers (Dataset::CreateValid, dataset.h:721) or fit one
    per column from `sample_col(j)` (DatasetLoader sampling + binning,
    dataset_loader.cpp:653-707)."""
    if reference is not None:
        ds.mappers = reference.mappers
        ds.real_feature_index = reference.real_feature_index
        ds.used_feature_map = reference.used_feature_map
        ds.tier_perm = reference.tier_perm
        ds.reference = reference
        return
    num_cols = ds.num_total_features
    cat_set = set(int(c) for c in categorical_feature)
    if config.pre_partition and config.num_machines > 1:
        # pre-partitioned multi-rank data: each rank bins a FEATURE SLICE
        # from its local sample, mappers allgathered so every rank holds
        # the identical set (dataset_loader.cpp:741)
        from .dist_binning import distributed_find_mappers
        sample_mat = np.column_stack(
            [np.asarray(sample_col(j), np.float64)
             for j in range(num_cols)])
        mappers = distributed_find_mappers(sample_mat, n_sample, config,
                                           sorted(cat_set))
        ds.mappers, ds.real_feature_index, ds.used_feature_map = [], [], []
        for j, m in enumerate(mappers):
            if m.is_trivial:
                ds.used_feature_map.append(-1)
            else:
                ds.used_feature_map.append(len(ds.mappers))
                ds.mappers.append(m)
                ds.real_feature_index.append(j)
        _apply_tier_order(ds)
        return
    max_bins = list(config.max_bin_by_feature) if config.max_bin_by_feature \
        else [config.max_bin] * num_cols
    ds.mappers, ds.real_feature_index, ds.used_feature_map = [], [], []
    for j in range(num_cols):
        bin_type = (BIN_TYPE_CATEGORICAL if j in cat_set
                    else BIN_TYPE_NUMERICAL)
        m = BinMapper.find_bin(
            sample_col(j), total_sample_cnt=n_sample,
            max_bin=max_bins[j],
            min_data_in_bin=config.min_data_in_bin,
            min_split_data=config.min_data_in_leaf,
            pre_filter=config.feature_pre_filter,
            bin_type=bin_type,
            use_missing=config.use_missing,
            zero_as_missing=config.zero_as_missing)
        if m.is_trivial:
            ds.used_feature_map.append(-1)
        else:
            ds.used_feature_map.append(len(ds.mappers))
            ds.mappers.append(m)
            ds.real_feature_index.append(j)
    if not ds.mappers:
        log_warning("There are no meaningful features which satisfy the "
                    "provided configuration. Decrease min_data_in_bin or "
                    "check the data.")
    _apply_tier_order(ds)


def _alloc_binned(ds: BinnedDataset) -> np.ndarray:
    max_num_bin = max((m.num_bin for m in ds.mappers), default=2)
    dtype = np.uint8 if max_num_bin <= 256 else np.uint16
    return np.zeros((ds.num_data, max(len(ds.mappers), 1)), dtype=dtype)


def ingest_bin_table(ds: BinnedDataset, config: Config, n_rows: int):
    """Device-ingest gate (docs/PERF.md §8): resolve ``binning_impl``
    (autotune-refined when the knob stayed "auto") and pack the
    train-mode bin table over ``ds.mappers``; None keeps the host
    per-feature ``value_to_bin`` loop. Callers additionally require f32
    raw input — binning f64 on device could round away precision the
    host path keeps, so f64 always stays host."""
    from ..ops.bucketize import (BinningUnavailable, pack_bin_table,
                                 resolve_binning_impl)
    if not ds.mappers:
        return None
    impl = None
    if config.binning_impl == "auto" and config.autotune:
        from ..runtime.autotune import autotune_binning_decision
        decision = autotune_binning_decision(
            ds.mappers, n_rows=n_rows, n_features=len(ds.mappers),
            max_bin=config.max_bin, num_leaves=config.num_leaves,
            cache_path=config.autotune_cache,
            seed=int(config.seed or 0))
        impl = decision.get("binning_impl")
        if impl:
            log_info(f"autotune: binning probe picked "
                     f"binning_impl='{impl}'")
    if impl is None:
        impl = resolve_binning_impl(config.binning_impl)
    if impl != "device":
        return None
    try:
        return pack_bin_table(ds.mappers, mode="train")
    except BinningUnavailable as e:
        log_warning(f"device binning unavailable ({e}); falling back "
                    "to host binning")
        return None


def _finalize(ds: BinnedDataset, config: Config,
              label, weight, group, init_score,
              reference: Optional[BinnedDataset]) -> BinnedDataset:
    """Metadata attach + the EFB bundle gate, shared by every
    constructor."""
    md = Metadata(ds.num_data)
    md.set_label(label)
    md.set_weight(weight)
    md.set_group(group)
    md.set_init_score(init_score)
    ds.metadata = md
    if (reference is None and config.enable_bundle
            and config.boosting in ("gbdt", "gbrt")
            and config.tpu_grower in ("auto", "wave", "wave_exact")):
        _build_bundles(ds, config)
    return ds


def construct_from_matrix(
    data: np.ndarray,
    config: Config,
    label: Optional[np.ndarray] = None,
    weight: Optional[np.ndarray] = None,
    group: Optional[np.ndarray] = None,
    init_score: Optional[np.ndarray] = None,
    categorical_feature: Sequence[int] = (),
    feature_names: Optional[Sequence[str]] = None,
    reference: Optional[BinnedDataset] = None,
) -> BinnedDataset:
    """Build a BinnedDataset from a raw [num_data, num_features] matrix
    (reference call stack: DatasetLoader::ConstructFromSampleData,
    src/io/dataset_loader.cpp:653-707 sampling + binning, then row push).

    With `reference` given, reuses its bin mappers so validation data aligns
    bin-for-bin with the training set (reference: Dataset::CreateValid,
    dataset.h:721).
    """
    data = np.asarray(data)
    if data.ndim != 2:
        log_fatal("Training data must be 2-dimensional")
    num_data, num_cols = data.shape
    with span("dataset/construct", rows=num_data, features=num_cols):
        ds = _init_ds(num_data, num_cols, config, feature_names)

        # sample rows for binning (bin_construct_sample_cnt rows,
        # dataset_loader.cpp:1162)
        with span("dataset/sample"):
            sample_cnt = min(config.bin_construct_sample_cnt, num_data)
            rng = np.random.RandomState(config.data_random_seed)
            if sample_cnt < num_data:
                sample_idx = np.sort(rng.choice(num_data, sample_cnt,
                                                replace=False))
                sample = data[sample_idx]
            else:
                sample = data
            sample = np.asarray(sample, dtype=np.float64)
        with span("dataset/find_bins"):
            _fit_or_adopt_mappers(ds, config, reference,
                                  lambda j: sample[:, j], len(sample),
                                  categorical_feature)

        # push rows: device bucketize when the raw matrix is f32 and the
        # mapper set packs (bit-identical to the host loop — docs/PERF.md
        # §8); per-feature vectorized value->bin on host otherwise
        X = _alloc_binned(ds)
        table = ingest_bin_table(ds, config, num_data) \
            if data.dtype == np.float32 else None
        span_count(binned_on_device=int(table is not None))
        if table is not None:
            from ..ops.bucketize import bin_rows_device
            with span("dataset/bucketize"):
                raw = np.ascontiguousarray(data[:, ds.real_feature_index],
                                           np.float32)
                X[:, :] = bin_rows_device(raw, table).astype(X.dtype)
            ds.binned_on = "device"
        else:
            with span("dataset/host_bin"):
                for inner, (m, orig) in enumerate(
                        zip(ds.mappers, ds.real_feature_index)):
                    col = np.asarray(data[:, orig], dtype=np.float64)
                    X[:, inner] = m.value_to_bin(col).astype(X.dtype)
        ds.X_binned = X
        if config.linear_tree:
            ds.raw_data = np.ascontiguousarray(data, dtype=np.float32)
        with span("dataset/finalize"):
            return _finalize(ds, config, label, weight, group, init_score,
                             reference)


def construct_from_sequences(
    seqs,
    config: Config,
    label: Optional[np.ndarray] = None,
    weight: Optional[np.ndarray] = None,
    group: Optional[np.ndarray] = None,
    init_score: Optional[np.ndarray] = None,
    categorical_feature: Sequence[int] = (),
    feature_names: Optional[Sequence[str]] = None,
    reference: Optional[BinnedDataset] = None,
) -> BinnedDataset:
    """Out-of-core two-round construction from user Sequence sources
    (reference: python Sequence class basic.py:841 + the loader's
    two-round/low-memory path, dataset_loader.cpp:1162-1213): round one
    samples rows for binning, round two streams batches through
    value_to_bin — peak memory is the 1-byte-per-cell binned matrix plus
    one raw batch, never the full raw data."""
    lens = [len(s) for s in seqs]
    num_data = int(sum(lens))
    if num_data == 0:
        log_fatal("Sequence sources are empty")
    with span("dataset/construct", rows=num_data):
        probe = np.asarray(seqs[0][0:1], dtype=np.float64)
        ds = _init_ds(num_data, probe.shape[1], config, feature_names)
        starts = np.concatenate([[0], np.cumsum(lens)])
        b = getattr(seqs[0], "batch_size", None) or 65536

        def fetch(global_lo, global_hi):
            """Rows [global_lo, global_hi) across the concatenated sources."""
            parts = []
            for si, s in enumerate(seqs):
                lo = max(global_lo, starts[si])
                hi = min(global_hi, starts[si + 1])
                if lo < hi:
                    parts.append(np.asarray(
                        s[int(lo - starts[si]):int(hi - starts[si])],
                        dtype=np.float64))
            return parts[0] if len(parts) == 1 else np.concatenate(parts)

        if reference is None:
            # round 1: sample rows (contiguous batched fetches of a random
            # global index set, dataset_loader.cpp:1162)
            sample_cnt = min(config.bin_construct_sample_cnt, num_data)
            rng = np.random.RandomState(config.data_random_seed)
            idx = np.sort(rng.choice(num_data, sample_cnt, replace=False)) \
                if sample_cnt < num_data else np.arange(num_data)
            chunks = []
            for lo in range(0, num_data, b):
                sel = idx[(idx >= lo) & (idx < lo + b)]
                if sel.size:
                    batch = fetch(lo, min(lo + b, num_data))
                    chunks.append(batch[sel - lo])
            sample = np.concatenate(chunks)
        else:
            sample = probe
        with span("dataset/find_bins"):
            _fit_or_adopt_mappers(ds, config, reference,
                                  lambda j: sample[:, j], len(sample),
                                  categorical_feature)

        # round 2: stream batches through the mappers
        X = _alloc_binned(ds)
        for lo in range(0, num_data, b):
            hi = min(lo + b, num_data)
            batch = fetch(lo, hi)
            for inner, (m, orig) in enumerate(
                    zip(ds.mappers, ds.real_feature_index)):
                X[lo:hi, inner] = m.value_to_bin(
                    batch[:, orig]).astype(X.dtype)
        ds.X_binned = X
        return _finalize(ds, config, label, weight, group, init_score,
                         reference)


def construct_from_sparse(
    data,
    config: Config,
    label: Optional[np.ndarray] = None,
    weight: Optional[np.ndarray] = None,
    group: Optional[np.ndarray] = None,
    init_score: Optional[np.ndarray] = None,
    categorical_feature: Sequence[int] = (),
    feature_names: Optional[Sequence[str]] = None,
    reference: Optional[BinnedDataset] = None,
) -> BinnedDataset:
    """Build from a scipy CSR/CSC matrix without densifying it: one raw
    column is materialized at a time (absent entries are 0, matching the
    reference's sparse semantics, sparse_bin.hpp; storage compression of
    the BINNED matrix comes from EFB bundling, dataset.cpp:251)."""
    num_data, num_cols = data.shape
    with span("dataset/construct", rows=num_data, features=num_cols):
        ds = _init_ds(num_data, num_cols, config, feature_names)
        csc = data.tocsc()

        if reference is None:
            sample_cnt = min(config.bin_construct_sample_cnt, num_data)
            rng = np.random.RandomState(config.data_random_seed)
            idx = np.sort(rng.choice(num_data, sample_cnt, replace=False)) \
                if sample_cnt < num_data else np.arange(num_data)
            sample = data.tocsr()[idx].tocsc()
            n_sample = len(idx)
        else:
            sample, n_sample = None, 0
        with span("dataset/find_bins"):
            _fit_or_adopt_mappers(
                ds, config, reference,
                lambda j: np.asarray(sample[:, j].todense(),
                                     np.float64).ravel(),
                n_sample, categorical_feature)

        X = _alloc_binned(ds)
        for inner, (m, orig) in enumerate(zip(ds.mappers,
                                              ds.real_feature_index)):
            col = np.asarray(csc[:, orig].todense(), np.float64).ravel()
            X[:, inner] = m.value_to_bin(col).astype(X.dtype)
        ds.X_binned = X
        return _finalize(ds, config, label, weight, group, init_score,
                         reference)


def load_binary_file(path: str, config: Config) -> BinnedDataset:
    """Load a binary dataset cache written by Dataset.save_binary
    (reference: DatasetLoader::LoadFromBinFile, dataset_loader.h:53 —
    skips sampling/binning entirely; the mappers ride in the file)."""
    import json
    from .binning import BinMapper
    z = np.load(path, allow_pickle=False)
    ds = BinnedDataset()
    ds.X_binned = z["X_binned"]
    ds.num_data = int(ds.X_binned.shape[0])
    ds.mappers = [BinMapper.from_dict(d)
                  for d in json.loads(str(z["mappers"]))]
    ds.real_feature_index = [int(v) for v in z["real_feature_index"]]
    ds.used_feature_map = [int(v) for v in z["used_feature_map"]]
    ds.feature_names = json.loads(str(z["feature_names"]))
    ds.num_total_features = int(z["num_total_features"])
    ds.max_bin = config.max_bin
    md = Metadata(ds.num_data)
    if z["label"].size:
        md.set_label(z["label"])
    if z["weight"].size:
        md.set_weight(z["weight"])
    if z["query_boundaries"].size:
        md.query_boundaries = np.asarray(z["query_boundaries"], np.int64)
    if "init_score" in z.files and z["init_score"].size:
        md.set_init_score(z["init_score"])
    ds.metadata = md
    # caches written before the tier reorder existed hold original-order
    # columns; re-applying to a tier-ordered cache is the identity
    _apply_tier_order(ds, reorder_binned=True)
    if (config.enable_bundle and config.boosting in ("gbdt", "gbrt")
            and config.tpu_grower in ("auto", "wave", "wave_exact")):
        _build_bundles(ds, config)
    return ds


def _build_bundles(ds: BinnedDataset, config: Config) -> None:
    """Exclusive Feature Bundling (reference: FindGroups dataset.cpp:112,
    FastFeatureBundling :251): greedily pack features whose non-default
    rows (almost) never overlap into shared uint8 columns. Histogram and
    row-scan work then scales with the number of BUNDLES; per-feature
    histograms are recovered at search time by slicing bundle offsets,
    with the default bin reconstructed via histogram fix-up
    (Dataset::FixHistogram, dataset.h:778)."""
    F = len(ds.mappers)
    N = ds.num_data
    if F <= 1 or N == 0 or ds.X_binned.dtype != np.uint8:
        return
    X = ds.X_binned
    # sample rows for conflict counting (the reference counts on its
    # binning sample)
    s_cnt = min(N, 50_000)
    if s_cnt < N:
        rng = np.random.RandomState(config.data_random_seed)
        srows = np.sort(rng.choice(N, s_cnt, replace=False))
        Xs = X[srows]
    else:
        Xs = X
    db = np.array([m.default_bin for m in ds.mappers], np.int64)
    nb = np.array([m.num_bin for m in ds.mappers], np.int64)
    is_cat = np.array([m.bin_type == BIN_TYPE_CATEGORICAL
                       for m in ds.mappers])
    nondef = Xs != db[None, :]
    nz = nondef.sum(axis=0)
    # reference constants (dataset.cpp:118-121)
    max_search_group = 100
    max_bin_per_group = 256
    max_conflict = s_cnt // 10_000
    order = np.argsort(-nz, kind="stable")
    groups: List[dict] = []
    for f in order:
        f = int(f)
        if is_cat[f] or nb[f] >= max_bin_per_group:
            groups.append(dict(members=[f], mask=None, bins=int(nb[f]),
                               conflicts=0))
            continue
        placed = False
        for g in groups[:max_search_group]:
            if g["mask"] is None:
                continue
            if g["bins"] + int(nb[f]) - 1 > max_bin_per_group:
                continue
            conflict = int(np.count_nonzero(nondef[:, f] & g["mask"]))
            if g["conflicts"] + conflict <= max_conflict:
                g["members"].append(f)
                g["mask"] |= nondef[:, f]
                g["bins"] += int(nb[f]) - 1
                g["conflicts"] += conflict
                placed = True
                break
        if not placed:
            groups.append(dict(members=[f], mask=nondef[:, f].copy(),
                               bins=1 + int(nb[f]) - 1, conflicts=0))
    n_bundled = sum(1 for g in groups if len(g["members"]) > 1)
    if n_bundled == 0:
        return
    # stable-sort bundle columns by histogram lane-width class so the
    # bundled storage keeps the tier-contiguity the inner-feature reorder
    # established (docs/PERF.md); g["bins"] is the column's bin count for
    # singletons and multi-bundles alike
    groups.sort(key=lambda g: _lane_width(g["bins"]))
    bundle_col = np.zeros(F, np.int32)
    bundle_off = np.full(F, -1, np.int32)
    cols = []
    bundles = []
    for ci, g in enumerate(groups):
        members = g["members"]
        bundles.append(list(members))
        if len(members) == 1:
            f = members[0]
            bundle_col[f] = ci
            cols.append(X[:, f])
            continue
        col = np.zeros(N, np.uint8)
        off = 1                       # bundle bin 0 = every member default
        for f in members:
            b = X[:, f].astype(np.int64)
            nd = b != db[f]
            rb = b - (b > db[f])      # compact out the default bin
            col[nd] = (off + rb[nd]).astype(np.uint8)
            bundle_col[f] = ci
            bundle_off[f] = off
            off += int(nb[f]) - 1
        cols.append(col)
    ds.bundles = bundles
    ds.X_bundled = np.ascontiguousarray(np.stack(cols, axis=1))
    ds.bundle_col = bundle_col.tolist()
    ds.bundle_off = bundle_off.tolist()
    from ..utils.log import log_info
    log_info(f"EFB: bundled {F} features into {len(groups)} columns "
             f"({n_bundled} multi-feature bundles)")
