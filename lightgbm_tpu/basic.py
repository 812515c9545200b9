"""Dataset and Booster: the core user-facing classes.

API mirrors the reference python package (python-package/lightgbm/basic.py:
Dataset:1692, Booster:3495) with the ctypes/C-API layer replaced by direct
calls into the JAX/NumPy core. Dataset keeps the reference's lazy-construction
semantics: raw data is held until `construct()` bins it (against an optional
reference dataset so validation bins align, basic.py _lazy_init).
"""

from __future__ import annotations

import copy
import os
from typing import Any, Dict, Iterable, List, Optional, Union

import numpy as np

from .config import Config, resolve_params
from .data.dataset import (BinnedDataset, construct_from_matrix,
                           construct_from_sequences, load_binary_file)
from .metrics import Metric, create_metric, default_metric_for_objective
from .models.gbdt import GBDT
from .objectives import create_objective
from .runtime.device import start_backend
from .runtime.profiler import (compiles_outside_spans,
                               count as span_count, span, spans,
                               spans_dropped)
from .utils.log import log_fatal, log_info, log_warning

# streaming device bin table "not yet resolved" marker (None is the
# meaningful "host path" answer, so it can't double as the sentinel)
_UNRESOLVED = object()


def _is_arrow(data: Any) -> bool:
    mod = type(data).__module__
    return mod.startswith("pyarrow")


def _is_scipy_sparse(data: Any) -> bool:
    return type(data).__module__.startswith("scipy.sparse")


def _arrow_to_numpy(data: Any) -> np.ndarray:
    """Arrow Table/RecordBatch/Array -> float64 matrix (reference:
    Arrow C-data ingestion, include/LightGBM/arrow.h:50,
    LGBM_DatasetCreateFromArrowStream c_api.h:477 — here the pyarrow
    objects are consumed directly; zero-copy per column when the type
    allows)."""
    import pyarrow as pa
    if isinstance(data, pa.RecordBatch):
        data = pa.Table.from_batches([data])
    if isinstance(data, pa.Table):
        cols = [np.asarray(c.to_numpy(zero_copy_only=False), np.float64)
                for c in data.columns]
        return np.column_stack(cols) if cols else np.zeros((0, 0))
    if isinstance(data, (pa.Array, pa.ChunkedArray)):
        return np.asarray(data.to_numpy(zero_copy_only=False),
                          np.float64).reshape(-1, 1)
    raise TypeError(f"Unsupported pyarrow input type {type(data)}")


def _to_1d_numpy(v: Any) -> np.ndarray:
    """Label/weight/init_score coercion incl. Arrow arrays (reference:
    Metadata Arrow setters, dataset.h:49-134)."""
    if _is_arrow(v):
        return _arrow_to_numpy(v).reshape(-1)
    return np.asarray(v).reshape(-1)


def _to_2d_numpy(data: Any) -> np.ndarray:
    if _is_arrow(data):
        return _arrow_to_numpy(data)
    if _is_scipy_sparse(data):
        # prediction-sized batches; Dataset construction routes sparse
        # through construct_from_sparse and never reaches here
        return np.asarray(data.todense())
    if hasattr(data, "values"):   # pandas DataFrame
        data = data.values
    arr = np.asarray(data)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    return arr


class Sequence:
    """Generic data access interface for out-of-core ingestion
    (reference: basic.py:841). Subclass with:

      * ``__len__()`` — number of rows
      * ``__getitem__(idx)`` — a row for an int, a 2-D batch for a slice

    and optionally set ``batch_size`` (rows fetched per binning batch).
    Pass an instance (or a list of instances, concatenated in order) as
    ``Dataset(data=...)``: construction samples rows for binning, then
    streams batches — the full raw matrix is never materialized."""

    batch_size: int = 65536

    def __len__(self) -> int:
        raise NotImplementedError

    def __getitem__(self, idx):
        raise NotImplementedError


class Dataset:
    """Dataset container (reference: basic.py:1692)."""

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List[int], List[str]] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True, position=None):
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = copy.deepcopy(params) if params else {}
        self.free_raw_data = free_raw_data
        self._handle: Optional[BinnedDataset] = None
        self.used_indices: Optional[np.ndarray] = None

    # ------------------------------------------------------------------
    def construct(self) -> "Dataset":
        if self._handle is not None:
            return self
        start_backend()
        cfg = resolve_params(self.params)

        # file-path data: binary cache (npz/zip magic) or text
        # (reference: Dataset(data=<path>) routes through DatasetLoader,
        # LoadFromBinFile when the signature matches, dataset_loader.h:53)
        if isinstance(self.data, (str, os.PathLike)):
            path = os.fspath(self.data)
            with open(path, "rb") as f:
                magic = f.read(4)
            if magic[:2] == b"PK":
                self._handle = load_binary_file(path, cfg)
                if self.reference is not None:
                    # the binary cache carries its own mappers; a
                    # reference can only be honored if they are identical
                    # (Dataset::CheckAlign semantics — raw data is gone,
                    # so re-binning against the reference is impossible)
                    self.reference.construct()
                    rh = self.reference._handle
                    ours = [m.to_dict() for m in self._handle.mappers]
                    refs = [m.to_dict() for m in rh.mappers]
                    if ours != refs:
                        log_fatal(
                            f"binary dataset {path} was saved with bin "
                            "mappers that differ from the reference "
                            "dataset's; rebuild the cache from a Dataset "
                            "constructed with reference=...")
                    self._handle.reference = rh
                for setter, val in ((self._handle.metadata.set_label,
                                     self.label),
                                    (self._handle.metadata.set_weight,
                                     self.weight)):
                    if val is not None:
                        setter(np.asarray(val))
                if self.group is not None:
                    self._handle.metadata.set_group(np.asarray(self.group))
                if self.init_score is not None:
                    self._handle.metadata.set_init_score(
                        _to_1d_numpy(self.init_score))
                if self.free_raw_data:
                    self.data = None
                return self
            from .data.loader import load_text_file
            if cfg.two_round:
                # the reference's two_round trades a second file pass for
                # lower peak memory (dataset_loader.cpp); this loader
                # streams through the native parser in one pass with no
                # extra copy, so the flag changes nothing — say so
                # instead of silently swallowing it
                log_warning(
                    "two_round is accepted for compatibility; the TPU "
                    "loader is single-pass/streaming and results are "
                    "identical")
            X, y, w, g, names = load_text_file(
                path, has_header=cfg.header,
                label_column=cfg.label_column,
                weight_column=cfg.weight_column,
                group_column=cfg.group_column,
                ignore_column=cfg.ignore_column)
            self.data = X
            if self.label is None and y is not None:
                self.label = y
            if self.weight is None and w is not None:
                self.weight = w
            if self.group is None and g is not None:
                self.group = g
            if self.feature_name == "auto" and names:
                self.feature_name = names

        # out-of-core Sequence source(s) (reference: basic.py:841)
        seqs = None
        if isinstance(self.data, Sequence):
            seqs = [self.data]
        elif isinstance(self.data, (list, tuple)) and self.data \
                and all(isinstance(s, Sequence) for s in self.data):
            seqs = list(self.data)
        if seqs is not None:
            return self._construct_from_seqs(seqs, cfg)

        # scipy sparse: column-streamed construction, never densified
        if _is_scipy_sparse(self.data):
            from .data.dataset import construct_from_sparse
            feature_names = (list(self.feature_name)
                             if isinstance(self.feature_name, list)
                             else None)
            ref_handle = None
            if self.reference is not None:
                self.reference.construct()
                ref_handle = self.reference._handle
            self._handle = construct_from_sparse(
                self.data, cfg,
                label=(None if self.label is None
                       else _to_1d_numpy(self.label)),
                weight=(None if self.weight is None
                        else _to_1d_numpy(self.weight)),
                group=(None if self.group is None
                       else _to_1d_numpy(self.group)),
                init_score=(None if self.init_score is None
                            else _to_1d_numpy(self.init_score)),
                categorical_feature=self._cat_indices(feature_names),
                feature_names=feature_names, reference=ref_handle)
            if self.free_raw_data:
                self.data = None
            return self

        data = _to_2d_numpy(self.data)
        n_cols = data.shape[1]

        feature_names: Optional[List[str]] = None
        if isinstance(self.feature_name, list):
            feature_names = list(self.feature_name)
        elif _is_arrow(self.data) and hasattr(self.data, "column_names"):
            feature_names = list(self.data.column_names)
        elif hasattr(self.data, "columns") \
                and not _is_arrow(self.data):
            feature_names = [str(c) for c in self.data.columns]

        cat_indices = self._cat_indices(feature_names)

        ref_handle = None
        if self.reference is not None:
            self.reference.construct()
            ref_handle = self.reference._handle

        label = None if self.label is None else _to_1d_numpy(self.label)
        weight = None if self.weight is None else _to_1d_numpy(self.weight)
        group = None if self.group is None else _to_1d_numpy(self.group)
        init_score = None if self.init_score is None else _to_1d_numpy(
            self.init_score)

        self._handle = construct_from_matrix(
            data, cfg, label=label, weight=weight, group=group,
            init_score=init_score, categorical_feature=cat_indices,
            feature_names=feature_names, reference=ref_handle)
        if self.free_raw_data:
            self.data = None
        return self

    def _cat_indices(self, feature_names: Optional[List[str]]) -> List[int]:
        cats = self.categorical_feature
        if cats == "auto" or cats is None:
            return []
        if isinstance(cats, str):
            return [int(c) for c in cats.split(",") if c]
        out: List[int] = []
        for c in cats:
            if isinstance(c, str):
                if feature_names and c in feature_names:
                    out.append(feature_names.index(c))
            else:
                out.append(int(c))
        return out

    def _construct_from_seqs(self, seqs: List["Sequence"],
                             cfg: Config) -> "Dataset":
        feature_names = (list(self.feature_name)
                         if isinstance(self.feature_name, list) else None)
        ref_handle = None
        if self.reference is not None:
            self.reference.construct()
            ref_handle = self.reference._handle
        # _to_1d_numpy (not plain asarray): pyarrow metadata arrays must
        # work on the Sequence path exactly like on the matrix path
        self._handle = construct_from_sequences(
            seqs, cfg,
            label=None if self.label is None else _to_1d_numpy(self.label),
            weight=(None if self.weight is None
                    else _to_1d_numpy(self.weight)),
            group=None if self.group is None else _to_1d_numpy(self.group),
            init_score=(None if self.init_score is None
                        else _to_1d_numpy(self.init_score)),
            categorical_feature=self._cat_indices(feature_names),
            feature_names=feature_names, reference=ref_handle)
        if self.free_raw_data:
            self.data = None
        return self

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None, position=None) -> "Dataset":
        """reference: basic.py Dataset.create_valid."""
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score,
                       params=params if params is not None else self.params)

    # -- introspection -------------------------------------------------
    def num_data(self) -> int:
        self.construct()
        return self._handle.num_data

    def num_feature(self) -> int:
        self.construct()
        return self._handle.num_total_features

    def get_label(self) -> Optional[np.ndarray]:
        if self._handle is not None:
            return self._handle.metadata.label
        return None if self.label is None else np.asarray(self.label)

    def get_weight(self) -> Optional[np.ndarray]:
        if self._handle is not None:
            return self._handle.metadata.weight
        return None if self.weight is None else np.asarray(self.weight)

    def get_group(self) -> Optional[np.ndarray]:
        if self._handle is not None and self._handle.metadata.query_boundaries is not None:
            return np.diff(self._handle.metadata.query_boundaries)
        return None if self.group is None else np.asarray(self.group)

    def get_init_score(self):
        return self.init_score

    def get_feature_name(self) -> List[str]:
        self.construct()
        return list(self._handle.feature_names)

    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._handle is not None:
            self._handle.metadata.set_label(
                None if label is None else np.asarray(label))
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._handle is not None:
            self._handle.metadata.set_weight(
                None if weight is None else np.asarray(weight))
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._handle is not None:
            self._handle.metadata.set_group(
                None if group is None else np.asarray(group))
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._handle is not None:
            self._handle.metadata.set_init_score(
                None if init_score is None else np.asarray(init_score))
        return self

    def subset(self, used_indices, params=None) -> "Dataset":
        """Row-subset Dataset sharing this one's bin mappers
        (reference: basic.py Dataset.subset -> Dataset::CopySubrow,
        dataset.h:674 — the bagging/CV subset path: no re-binning)."""
        self.construct()
        h = self._handle
        idx = np.asarray(used_indices, np.int64)
        sub = Dataset(None, params=(params if params is not None
                                    else self.params),
                      free_raw_data=self.free_raw_data)
        nh = BinnedDataset()
        nh.num_data = int(len(idx))
        nh.num_total_features = h.num_total_features
        nh.mappers = h.mappers
        nh.real_feature_index = h.real_feature_index
        nh.used_feature_map = h.used_feature_map
        nh.feature_names = list(h.feature_names)
        nh.max_bin = h.max_bin
        nh.reference = h
        nh.X_binned = h.X_binned[idx]
        from .data.dataset import Metadata
        md = Metadata(nh.num_data)
        if h.metadata.label is not None:
            md.set_label(h.metadata.label[idx])
        if h.metadata.weight is not None:
            md.set_weight(h.metadata.weight[idx])
        if h.metadata.init_score is not None:
            ins = np.asarray(h.metadata.init_score).reshape(-1)
            if ins.size == h.num_data:
                md.set_init_score(ins[idx])
            else:   # per-class init scores, class-major
                k = ins.size // h.num_data
                md.set_init_score(
                    ins.reshape(k, h.num_data)[:, idx].reshape(-1))
        # query boundaries survive whole-query subsets (the bagging-by-
        # query case CopySubrow serves); partial queries can't be
        # represented and are dropped with a warning
        if h.metadata.query_boundaries is not None:
            qb = np.asarray(h.metadata.query_boundaries)
            qid = np.searchsorted(qb, idx, side="right") - 1
            sel_q, counts = np.unique(qid, return_counts=True)
            full = np.all(counts == np.diff(qb)[sel_q])
            contiguous = np.all(np.diff(qid) >= 0)
            if full and contiguous:
                md.set_group(counts)
            else:
                log_warning("Dataset.subset dropped query boundaries: "
                            "the row subset does not keep queries whole")
        nh.metadata = md
        sub._handle = nh
        return sub

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Append `other`'s features to this dataset in place
        (reference: basic.py Dataset.add_features_from ->
        Dataset::AddFeaturesFrom, dataset.h:971). Both sides must be
        constructed with the same row count; `other`'s bin mappers ride
        along. EFB bundles are dropped and NOT rebuilt (bundling happens
        only at construction/binary load), so the merged dataset trains
        unbundled — correct results, without EFB's storage savings."""
        self.construct()
        other.construct()
        h, o = self._handle, other._handle
        if h.num_data != o.num_data:
            log_fatal("Cannot add features from a Dataset with "
                      f"{o.num_data} rows to one with {h.num_data}")
        off = h.num_total_features          # original-column offset
        inner_off = len(h.mappers)          # inner-feature offset
        h.X_binned = np.concatenate([h.X_binned[:, :len(h.mappers)],
                                     o.X_binned[:, :len(o.mappers)]],
                                    axis=1)
        h.mappers = list(h.mappers) + list(o.mappers)
        h.real_feature_index = list(h.real_feature_index) + [
            off + r for r in o.real_feature_index]
        h.used_feature_map = list(h.used_feature_map) + [
            (-1 if m < 0 else m + inner_off) for m in o.used_feature_map]
        # re-number default names and de-collide user names so name-based
        # column specs stay unambiguous
        new_names = []
        existing = set(h.feature_names)
        for r, name in enumerate(o.feature_names):
            if name == f"Column_{r}":
                name = f"Column_{off + r}"
            while name in existing:
                name = name + "_y"
            existing.add(name)
            new_names.append(name)
        h.feature_names = list(h.feature_names) + new_names
        h.num_total_features = off + o.num_total_features
        h.bundles = h.X_bundled = h.bundle_col = h.bundle_off = None
        return self

    # -- streaming push ingestion --------------------------------------
    def init_streaming(self, num_rows: int,
                       reference: Optional["Dataset"] = None) -> "Dataset":
        """Incremental row-push construction against a reference's bin
        mappers (reference: LGBM_DatasetInitStreaming c_api.cpp:1125 +
        LGBM_DatasetPushRows* c_api.h:221-324; streaming requires the
        schema/mappers up front, normally from a serialized reference).
        Falls back to `self.reference` when `reference` is None."""
        ref = reference if reference is not None else self.reference
        if ref is None:
            log_fatal("init_streaming requires a reference Dataset "
                      "carrying the bin mappers")
        ref.construct()
        rh = ref._handle
        h = BinnedDataset()
        h.num_data = int(num_rows)
        h.num_total_features = rh.num_total_features
        h.mappers = rh.mappers
        h.real_feature_index = rh.real_feature_index
        h.used_feature_map = rh.used_feature_map
        h.feature_names = list(rh.feature_names)
        h.max_bin = rh.max_bin
        h.reference = rh
        h.X_binned = np.zeros((num_rows, max(len(rh.mappers), 1)),
                              dtype=rh.X_binned.dtype)
        from .data.dataset import Metadata
        md = Metadata(num_rows)
        md.set_label(np.zeros(num_rows, np.float32))
        h.metadata = md
        self._handle = h
        self._stream_pos = 0
        self._stream_table = _UNRESOLVED
        return self

    def _stream_bin_table(self):
        """Packed train-mode device bin table for streaming pushes
        (ops/bucketize.py), resolved once per init_streaming from the
        dataset's ``binning_impl`` knob; None = host per-feature
        value_to_bin (docs/PERF.md §8)."""
        if self._stream_table is _UNRESOLVED:
            from .data.dataset import ingest_bin_table
            cfg = resolve_params(self.params)
            self._stream_table = ingest_bin_table(
                self._handle, cfg, self._handle.num_data)
        return self._stream_table

    def push_rows(self, data, label=None, weight=None, init_score=None,
                  start_row: Optional[int] = None) -> "Dataset":
        """Push a batch of raw rows into a streaming dataset, binning
        against the reference mappers (LGBM_DatasetPushRowsWithMetadata
        semantics; single-writer — the reference's C API allows
        concurrent pushers, here pushes are sequential)."""
        h = self._handle
        if h is None or not hasattr(self, "_stream_pos"):
            log_fatal("push_rows requires init_streaming first")
        batch = _to_2d_numpy(data)
        n = batch.shape[0]
        lo = self._stream_pos if start_row is None else int(start_row)
        hi = lo + n
        if hi > h.num_data:
            log_fatal(f"push_rows overflows the dataset "
                      f"({hi} > {h.num_data})")
        # f32 batches bucketize on device when the mapper set packs
        # (bit-identical to the host loop); f64 always stays host
        table = self._stream_bin_table() \
            if batch.dtype == np.float32 else None
        if table is not None:
            from .ops.bucketize import bin_rows_device
            bin_rows_device(h.X_binned[lo:hi, :], batch,
                            h.real_feature_index, table)
        else:
            for inner, (m, orig) in enumerate(zip(h.mappers,
                                                  h.real_feature_index)):
                h.X_binned[lo:hi, inner] = m.value_to_bin(
                    np.asarray(batch[:, orig], np.float64))
        if label is not None:
            h.metadata.label[lo:hi] = _to_1d_numpy(label)
        if weight is not None:
            if h.metadata.weight is None:
                h.metadata.set_weight(np.ones(h.num_data, np.float32))
            h.metadata.weight[lo:hi] = _to_1d_numpy(weight)
        if init_score is not None:
            if h.metadata.init_score is None:
                h.metadata.set_init_score(np.zeros(h.num_data, np.float64))
            h.metadata.init_score[lo:hi] = _to_1d_numpy(init_score)
        if start_row is None:
            self._stream_pos = hi
        else:
            self._stream_pos = max(self._stream_pos, hi)
        return self

    def mark_finished(self) -> "Dataset":
        """End of streaming pushes (LGBM_DatasetMarkFinished)."""
        if not hasattr(self, "_stream_pos"):
            log_fatal("mark_finished requires init_streaming first")
        if self._stream_pos < self._handle.num_data:
            log_warning(f"streaming dataset finished at row "
                        f"{self._stream_pos} of {self._handle.num_data}")
        del self._stream_pos
        return self

    def save_binary(self, filename: str) -> "Dataset":
        """Binary dataset cache (reference: LGBM_DatasetSaveBinary,
        c_api.h:540). Stored as an npz with mapper metadata."""
        self.construct()
        h = self._handle
        # pass a file object: savez would otherwise append ".npz"
        with open(filename, "wb") as fout:
            self._write_binary(fout, h)
        return self

    def _write_binary(self, fout, h) -> None:
        import json
        np.savez_compressed(
            fout,
            X_binned=h.X_binned,
            label=h.metadata.label if h.metadata.label is not None else np.zeros(0),
            weight=h.metadata.weight if h.metadata.weight is not None else np.zeros(0),
            query_boundaries=(h.metadata.query_boundaries
                              if h.metadata.query_boundaries is not None
                              else np.zeros(0)),
            init_score=(h.metadata.init_score
                        if h.metadata.init_score is not None
                        else np.zeros(0)),
            mappers=json.dumps([m.to_dict() for m in h.mappers]),
            real_feature_index=np.asarray(h.real_feature_index),
            used_feature_map=np.asarray(h.used_feature_map),
            feature_names=json.dumps(h.feature_names),
            num_total_features=h.num_total_features,
        )


class Booster:
    """Booster (reference: basic.py:3495)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None):
        self.params = copy.deepcopy(params) if params else {}
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._train_metrics: List[Metric] = []
        self._valid_metrics: List[List[Metric]] = []
        self.name_valid_sets: List[str] = []

        if train_set is not None:
            cfg = resolve_params(self.params)
            # multi-host bring-up (reference: Booster.__init__ network setup
            # from the `machines` param, python-package basic.py:3531-3563)
            if cfg.num_machines > 1 or cfg.machines:
                from .parallel import init_distributed
                init_distributed(machines=cfg.machines,
                                 num_machines=cfg.num_machines)
            train_set.params = {**train_set.params, **self.params} \
                if train_set._handle is None else train_set.params
            train_set.construct()
            objective = create_objective(cfg)
            metric_names = cfg.metric or [default_metric_for_objective(
                cfg.objective)]
            self._train_metrics = [
                m for m in (create_metric(n, cfg) for n in metric_names)
                if m is not None]
            from .models import create_boosting
            self._gbdt = create_boosting(cfg, train_set._handle, objective,
                                         self._train_metrics)
            self.train_set = train_set
            self._config = cfg
            self._metric_names = metric_names
        elif model_file is not None:
            with open(model_file) as f:
                model_str = f.read()
            self._gbdt = GBDT.load_model_from_string(model_str)
            self._config = self._gbdt.config
        elif model_str is not None:
            self._gbdt = GBDT.load_model_from_string(model_str)
            self._config = self._gbdt.config
        else:
            raise ValueError("need at least one of train_set, model_file "
                             "and model_str")

    # ------------------------------------------------------------------
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        if data.reference is None or data.reference is not self.train_set:
            data.reference = self.train_set
        data.construct()
        metrics = [m for m in (create_metric(n, self._config)
                               for n in self._metric_names) if m is not None]
        with span("booster/add_valid", rows=data._handle.num_data):
            self._gbdt.add_valid_dataset(data._handle, name, metrics)
        self._valid_metrics.append(metrics)
        self.name_valid_sets.append(name)
        return self

    def update(self, train_set: Optional[Dataset] = None,
               fobj=None) -> bool:
        """One boosting iteration (reference: basic.py:4005). Returns True
        when no further splits are possible."""
        if fobj is not None:
            K = self._gbdt.num_tree_per_iteration
            score = self.__inner_raw_score()
            grad, hess = fobj(score, self.train_set)
            return self._gbdt.train_one_iter(np.asarray(grad),
                                             np.asarray(hess))
        return self._gbdt.train_one_iter()

    def update_batch(self, n: int, chunk: Optional[int] = None):
        """Run `n` boosting iterations with whole-chunk device scans (no
        host round-trip per iteration) when semantics allow, else fall
        back to per-iteration updates. TPU-native extension; the
        reference's per-iteration C API boundary (LGBM_BoosterUpdateOneIter)
        has no batched analog.

        Tail iterations (n % chunk) run through the SAME compiled scan,
        padded to the chunk size with inert steps, so a single executable
        covers every chunk regardless of n (docs/PERF.md §7).

        Returns what the scans computed of the valid sets' metrics: a
        device array [iterations run in a scan, M], one row a tree, the
        columns under ``batched_eval_layout()``'s names; None where no
        valid metric rides in the scan (or no iteration ran in one)."""
        if self._gbdt._stopped:
            return None
        if chunk is None:
            chunk = self._config.batched_chunk_size
        done = 0
        chunks_done = 0
        evals = []

        def results():
            if not evals:
                return None
            import jax.numpy as jnp
            return evals[0] if len(evals) == 1 else jnp.concatenate(evals)
        if self._gbdt.can_batch_iters(min(n, chunk)):
            n_chunks = (n + chunk - 1) // chunk
            while done < n:
                step = min(chunk, n - done)
                if not self._gbdt.can_batch_iters(step):
                    # a host-mode resample falls inside THIS chunk's
                    # window; finish the remainder per-iteration
                    break
                mvals = self._gbdt.train_iters_batched(step, n_pad=chunk)
                if mvals is not None:
                    evals.append(mvals)
                done += step
                chunks_done += 1
                # amortized no-more-splits check (one sync) at power-of-2
                # chunk counts, mirroring train_one_iter's policy. The
                # FIRST chunk is exempt (a 32-iteration run cannot
                # plausibly exhaust splits, and the sync costs a full
                # device drain); so is the last chunk,
                # whose trees are already queued either way.
                if chunks_done > 1 and chunks_done < n_chunks \
                        and (chunks_done & (chunks_done - 1)) == 0 \
                        and self._gbdt._check_stopped():
                    self._gbdt._stopped = True
                    return results()
        for _ in range(n - done):
            if self.update():
                break
        return results()

    def batched_eval_layout(self):
        """(valid_name, result_name, higher_better) per column of what
        ``update_batch`` returns; None where some valid metric has no
        device analog."""
        return self._gbdt.batched_eval_layout()

    def __inner_raw_score(self) -> np.ndarray:
        import jax
        # slice off data-parallel padding rows (scores are [K, N_pad])
        s = np.asarray(
            jax.device_get(self._gbdt.scores))[:, :self._gbdt.num_data]
        return s[0] if s.shape[0] == 1 else s.reshape(-1)

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    @property
    def current_iteration(self):
        return self._gbdt.iter

    def num_trees(self) -> int:
        return len(self._gbdt.models)

    def get_profile(self) -> Optional[Dict[str, Any]]:
        """Device-profile export (runtime/profiler.py to_dict): per-stage
        seconds, per-iteration ring buffer, row-iters/s, HBM watermark,
        and under "spans" the process's unfenced span ring (beside it
        the backend compilations that fired under no span, and the
        spans pushed out of the full ring).
        None unless trained with device_profile=true."""
        prof = getattr(self._gbdt, "profiler", None)
        if prof is None:
            return None
        return dict(prof.to_dict(), spans=spans(),
                    compiles_outside_spans=compiles_outside_spans(),
                    spans_dropped=spans_dropped())

    def num_model_per_iteration(self) -> int:
        return self._gbdt.num_tree_per_iteration

    def num_feature(self) -> int:
        return self._gbdt.max_feature_idx_ + 1

    def feature_name(self) -> List[str]:
        return list(self._gbdt.feature_names_)

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        t = 0 if importance_type == "split" else 1
        imp = self._gbdt.feature_importance(t, iteration or -1)
        return imp if t else imp.astype(np.int64)

    # ------------------------------------------------------------------
    def eval_train(self, feval=None) -> List:
        return self.__eval("training", feval)

    def eval_valid(self, feval=None) -> List:
        out = []
        for name in self.name_valid_sets:
            out.extend(self.__eval(name, feval))
        return out

    def eval(self, data: Dataset, name: str, feval=None) -> List:
        if name == "training":
            return self.eval_train(feval)
        return self.__eval(name, feval)

    def __eval(self, name: str, feval=None) -> List:
        if name == "training":
            metrics = {name: self._train_metrics}
        else:
            vi = self.name_valid_sets.index(name)
            metrics = {name: self._valid_metrics[vi]}
        res = self._gbdt.get_eval_result(metrics)
        if feval is not None:
            import jax
            if name == "training":
                score = np.asarray(
                    jax.device_get(self._gbdt.scores))[:, :self._gbdt.num_data]
                dataset = self.train_set
            else:
                vi = self.name_valid_sets.index(name)
                score = np.asarray(
                    jax.device_get(self._gbdt._valid_scores[vi]))
                dataset = None
            s = score[0] if score.shape[0] == 1 else score.reshape(-1)
            ret = feval(s, dataset)
            if ret is not None:
                if isinstance(ret, tuple):
                    ret = [ret]
                for mn, val, hib in ret:
                    res.append((name, mn, val, hib))
        return res

    # ------------------------------------------------------------------
    def predict(self, data, start_iteration: int = 0,
                num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, **kwargs) -> np.ndarray:
        with span("predict"):
            with span("predict/to_numpy"):
                data = _to_2d_numpy(data)
            span_count(rows=data.shape[0], features=data.shape[1],
                       bytes_in=data.nbytes)
            ni = num_iteration if num_iteration is not None else (
                self.best_iteration if self.best_iteration > 0 else -1)
            if pred_leaf:
                return self._gbdt.predict_leaf_index(data, start_iteration,
                                                     ni)
            if pred_contrib:
                from .models.shap import predict_contrib
                return predict_contrib(self._gbdt, data, start_iteration,
                                       ni)
            es_kwargs = {}
            for p in ("pred_early_stop", "pred_early_stop_freq",
                      "pred_early_stop_margin"):
                if p in kwargs:
                    es_kwargs[p] = kwargs[p]
                elif p in self.params:
                    es_kwargs[p] = self.params[p]
            return self._gbdt.predict(data, raw_score=raw_score,
                                      start_iteration=start_iteration,
                                      num_iteration=ni, **es_kwargs)

    def serve(self, **kwargs) -> "Any":
        """Production inference session over this model: pinned packed
        trees, per-bucket compiled predictor cache, optional multi-device
        sharding (serving/session.py, docs/SERVING.md). Host-engine
        outputs are bit-identical to :meth:`predict`."""
        from .serving import ServingSession
        return ServingSession.from_booster(self, **kwargs)

    # ------------------------------------------------------------------
    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> "Booster":
        # atomic (write-temp -> fsync -> rename): a concurrent reader —
        # the serving snapshot watcher in particular — can never observe
        # a half-written model file (docs/ROBUSTNESS.md)
        from .runtime.checkpoint import atomic_write_text
        atomic_write_text(filename,
                          self.model_to_string(num_iteration,
                                               start_iteration,
                                               importance_type))
        return self

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0,
                        importance_type: str = "split") -> str:
        ni = num_iteration if num_iteration is not None else (
            self.best_iteration if self.best_iteration > 0 else -1)
        s = self._gbdt.save_model_to_string(
            start_iteration, ni, 0 if importance_type == "split" else 1)
        return s + "\npandas_categorical:null\n"

    def model_from_string(self, model_str: str) -> "Booster":
        self._gbdt = GBDT.load_model_from_string(model_str)
        return self

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> Dict[str, Any]:
        """JSON model dump (reference: GBDT::DumpModel,
        gbdt_model_text.cpp:31)."""
        g = self._gbdt
        ni = num_iteration if num_iteration is not None else (
            self.best_iteration if self.best_iteration > 0 else -1)
        K = g.num_tree_per_iteration
        total_iters = len(g.models) // K if K else 0
        end = total_iters if ni <= 0 else min(total_iters,
                                              start_iteration + ni)
        trees = []
        for it in range(start_iteration, end):
            for k in range(K):
                d = g.models[it * K + k].to_json()
                d["tree_index"] = len(trees)
                trees.append(d)
        return {
            "name": "tree",
            "version": "v4",
            "num_class": g.num_class,
            "num_tree_per_iteration": K,
            "label_index": g.label_idx_,
            "max_feature_idx": g.max_feature_idx_,
            "objective": (g.objective.to_string() if g.objective else ""),
            "average_output": g.average_output,
            "feature_names": list(g.feature_names_),
            "feature_importances": {
                name: float(v) for name, v in zip(
                    g.feature_names_,
                    g.feature_importance(
                        0 if importance_type == "split" else 1))
                if v > 0},
            "tree_info": trees,
        }

    def refit(self, data, label, decay_rate: float = 0.9,
              weight=None, **kwargs) -> "Booster":
        """Refit existing tree structures to new data, returning a NEW
        Booster (the original is unchanged, like the reference python
        Booster.refit; leaf math per GBDT::RefitTree, gbdt.cpp:200-228):
        each leaf value becomes decay_rate * old + (1 - decay_rate) * new,
        where `new` is the regularized leaf output of the new data's
        gradients falling in that leaf. ``weight`` scales per-row
        gradients/hessians exactly as at train time (docs/PARITY.md
        §Refit)."""
        data = _to_2d_numpy(data)
        new_booster = Booster(model_str=self.model_to_string())
        g = new_booster._gbdt
        if g.objective is None:
            raise ValueError("Cannot refit a model without an objective")
        # restore training regularization (the model string only carries the
        # objective); refit-time params override
        cfg = resolve_params({**self.params, **kwargs})
        g.config = cfg
        label = np.asarray(label, np.float32).reshape(-1)
        K = g.num_tree_per_iteration
        N = data.shape[0]
        # leaf assignment per tree for the new data
        leaf_preds = self.predict(data, pred_leaf=True).reshape(N, -1)
        from .data.dataset import Metadata
        md = Metadata(N)
        md.set_label(label)
        if weight is not None:
            md.set_weight(np.asarray(weight, np.float32).reshape(-1))
        g.objective.init(md, N)
        scores = np.zeros((K, N), dtype=np.float64)
        import jax.numpy as jnp
        total_iters = len(g.models) // max(K, 1)
        for it in range(total_iters):
            # gradients ONCE per iteration, before any class's score update
            # (reference: GBDT::RefitTree calls Boosting() per iteration)
            if g.objective.runs_on_host:
                grads, hesss = g.objective.get_gradients_numpy(
                    scores.reshape(-1).astype(np.float64))
                grads = grads.reshape(K, N)
                hesss = hesss.reshape(K, N)
            else:
                gg, hh = g.objective.get_gradients(
                    jnp.asarray(scores[0] if K == 1 else scores,
                                jnp.float32),
                    jnp.asarray(label),
                    None if md.weight is None else jnp.asarray(md.weight))
                grads = np.asarray(gg).reshape(K, N) \
                    if np.asarray(gg).ndim > 1 \
                    else np.asarray(gg).reshape(1, N)
                hesss = np.asarray(hh).reshape(K, N) \
                    if np.asarray(hh).ndim > 1 \
                    else np.asarray(hh).reshape(1, N)
            for k in range(K):
                mi = it * K + k
                tree = g.models[mi]
                leaf = leaf_preds[:, mi]
                nl = tree.num_leaves
                sum_g = np.bincount(leaf, weights=grads[k], minlength=nl)
                sum_h = np.bincount(leaf, weights=hesss[k], minlength=nl)
                reg = np.abs(sum_g) - cfg.lambda_l1
                new_val = -np.sign(sum_g) * np.maximum(reg, 0.0) / (
                    sum_h + cfg.lambda_l2 + 1e-15)
                new_val *= tree.shrinkage
                tree.leaf_value = (decay_rate * tree.leaf_value
                                   + (1.0 - decay_rate) * new_val[:nl])
                if getattr(tree, "is_linear", False):
                    # reference: FitByExistingTree then
                    # CalculateLinear(is_refit=true) with decay
                    # (linear_tree_learner.cpp:139-156,330-390). The
                    # saved model's per-leaf feature sets are reused
                    # (tree->LeafFeatures), already numeric-filtered at
                    # train time, expressed as raw column ids.
                    from .models.linear import fit_linear_models
                    Ftot = data.shape[1]
                    # grads/hesss already carry the sample weight (the
                    # objective applies it); in_bag stays all-ones here
                    out = fit_linear_models(
                        tree, np.asarray(data, np.float32),
                        leaf.astype(np.int32), grads[k], hesss[k],
                        np.ones(N, np.float32),
                        linear_lambda=float(cfg.linear_lambda),
                        shrinkage=tree.shrinkage,
                        numeric_inner=np.ones(Ftot, bool),
                        inner_to_real=np.arange(Ftot, dtype=np.int64),
                        leaf_features_inner=tree.leaf_features,
                        is_refit=True, decay_rate=decay_rate)
                    scores[k] += out
                else:
                    scores[k] += tree.leaf_value[leaf]
        return new_booster

    def dump_model_to_cpp(self) -> str:
        """C++ if-else codegen (reference: GBDT::SaveModelToIfElse,
        gbdt_model_text.cpp:262). Handles missing semantics (None/Zero/NaN
        per Tree::NumericalDecision, tree.h:375-407) and categorical bitset
        splits (Tree::CategoricalDecision)."""
        from .models.predictor import (format_tree_indices,
                                       linear_tree_indices)
        linear = linear_tree_indices(self._gbdt.models)
        if linear:
            from .utils.log import log_fatal
            log_fatal("convert_model to C++ is not supported for linear "
                      f"trees: {format_tree_indices(linear)} carry fitted "
                      "linear leaf functions; retrain with "
                      "linear_tree=false")
        g = self._gbdt
        lines = ["#include <cmath>", "#include <cstdint>", "",
                 f"// generated by lightgbm_tpu; {len(g.models)} trees"]
        for i, tree in enumerate(g.models):
            # constant bitset tables for this tree's categorical splits
            if tree.num_cat > 0:
                for ci in range(tree.num_cat):
                    s0 = int(tree.cat_boundaries[ci])
                    s1 = int(tree.cat_boundaries[ci + 1])
                    words = ", ".join(
                        f"{int(w)}u" for w in tree.cat_threshold[s0:s1])
                    lines.append(
                        f"static const uint32_t kCatBits{i}_{ci}[] = "
                        f"{{{words}}};")
            lines.append(f"double PredictTree{i}(const double* arr) {{")
            if tree.num_leaves <= 1:
                lines.append(f"  return {float(tree.leaf_value[0])!r};")
            else:
                def emit(node, depth):
                    ind = "  " * (depth + 1)
                    if node < 0:
                        lines.append(
                            f"{ind}return "
                            f"{float(tree.leaf_value[~node])!r};")
                        return
                    f = int(tree.split_feature[node])
                    dt = int(tree.decision_type[node])
                    is_cat = bool(dt & 1)
                    default_left = bool(dt & 2)
                    missing_type = (dt >> 2) & 3
                    if is_cat:
                        # CategoricalDecision: NaN / negative / out-of-range
                        # fall right; otherwise bitset membership
                        ci = int(tree.threshold_in_bin[node])
                        nwords = int(tree.cat_boundaries[ci + 1]
                                     - tree.cat_boundaries[ci])
                        cond = (
                            f"(!std::isnan(arr[{f}]) && arr[{f}] >= 0 && "
                            f"static_cast<int>(arr[{f}]) < {nwords * 32} && "
                            f"((kCatBits{i}_{ci}"
                            f"[static_cast<int>(arr[{f}]) / 32] >> "
                            f"(static_cast<int>(arr[{f}]) % 32)) & 1))")
                    else:
                        thr = float(tree.threshold[node])
                        # NumericalDecision: NaN -> 0 unless missing_type is
                        # NaN; Zero-missing follows the default direction
                        val = f"(std::isnan(arr[{f}]) ? 0.0 : arr[{f}])"
                        if missing_type == 2:       # MissingType::NaN
                            miss = f"std::isnan(arr[{f}])"
                            val = f"arr[{f}]"
                        elif missing_type == 1:     # MissingType::Zero
                            miss = f"(std::fabs({val}) <= 1e-35)"
                        else:
                            miss = "false"
                        dirn = "true" if default_left else "false"
                        cond = (f"({miss} ? {dirn} : "
                                f"({val} <= {thr!r}))")
                    lines.append(f"{ind}if {cond} {{")
                    emit(int(tree.left_child[node]), depth + 1)
                    lines.append(f"{ind}}} else {{")
                    emit(int(tree.right_child[node]), depth + 1)
                    lines.append(f"{ind}}}")
                emit(0, 0)
            lines.append("}")
            lines.append("")
        n = len(g.models)
        lines.append("double Predict(const double* arr) {")
        lines.append("  double result = 0.0;")
        for i in range(n):
            lines.append(f"  result += PredictTree{i}(arr);")
        if g.average_output and n:
            lines.append(f"  result /= {n};")
        lines.append("  return result;")
        lines.append("}")
        return "\n".join(lines) + "\n"

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """reference: basic.py Booster.reset_parameter (supports the
        reset_parameter callback: learning-rate schedules etc.)."""
        self.params.update(params)
        cfg = resolve_params(self.params)
        self._gbdt.config = cfg
        self._gbdt.shrinkage_rate = cfg.learning_rate
        return self

    def __copy__(self):
        return self

    def free_dataset(self) -> "Booster":
        return self

    def free_network(self) -> "Booster":
        return self
