"""Data-parallel tree training over a device mesh.

TPU-native re-design of DataParallelTreeLearner
(src/treelearner/data_parallel_tree_learner.cpp): rows are sharded across the
mesh `data` axis; each device builds histograms on its local shard; the
histogram Allreduce (reference: Network::ReduceScatter of histogram buffers +
Allgather of best splits, data_parallel_tree_learner.cpp:286-298 and
SyncUpGlobalBestSplit, parallel_tree_learner.h:210-233) becomes a single
`psum` over ICI inside the grower. Split selection then happens redundantly
but identically on every device, which reproduces the reference invariant:
every rank executes the same splits and grows the IDENTICAL tree
(SURVEY.md §3.4) — no split-record broadcast is needed at all.

The whole per-tree loop stays inside ONE jitted shard_map computation; the
only cross-device traffic is the per-split histogram exchange — a full
`psum` under `parallel_hist_mode=allreduce`, or a `psum_scatter` of the
feature-padded buffer plus a pmax best-split sync under
`parallel_hist_mode=reduce_scatter` (ops/grow.py, parallel/packed.py,
docs/PERF.md §Communication) — and scalar root reductions, matching the
wire profile of the reference's tree_learner=data (ReduceScatter +
SyncUpGlobalBestSplit rather than a monolithic Allreduce).
"""

from __future__ import annotations

import inspect

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops.grow import GrowConfig, grow_tree
from .context import DATA_AXIS, DistContext


def lane_multiple() -> int:
    """Device-derived row-pad granularity: TPU vector registers are
    (8, 128) tiles, so per-shard row counts that are multiples of 128
    avoid relayout padding inside every batched op; host/GPU backends
    tile fine at 8 (and 128 would waste real memory on tiny CPU-mesh
    tests)."""
    return 128 if jax.default_backend() == "tpu" else 8


def pad_rows_to(n: int, num_shards: int, multiple: int = 0) -> int:
    """Rows must split evenly across shards (and pad to a lane-friendly
    multiple per shard so XLA tiles cleanly). `multiple=0` (default)
    derives the granularity from the active backend via
    `lane_multiple`."""
    if multiple <= 0:
        multiple = lane_multiple()
    per = -(-n // num_shards)
    per = -(-per // multiple) * multiple
    return per * num_shards


def build_data_parallel_train_fn(mesh: jax.sharding.Mesh,
                                 cfg: GrowConfig,
                                 grow_fn=grow_tree,
                                 replicate_rows: bool = False):
    """Returns jit(train_step) with the same signature as the serial
    `_train_tree` in models/gbdt.py:

        (X_t [F,N], grad [N], hess [N], in_bag [N], scores_k [N], lr, mask[F],
         seed, meta: FeatureMeta)
        -> (DeviceTree replicated, leaf_of_row [N], new_scores [N])

    N must be divisible by the mesh's data-axis size (pad with in_bag == 0
    rows via `pad_rows_to`). `grow_fn` is either the masked grower
    (ops/grow.py) or the compacted one (ops/grow_fast.py). `meta`, the
    dataset's per-feature facts, is a replicated argument like the
    serial step's and never a constant of the program (models/gbdt.py
    `_build_jit_fns`).
    """
    dist = DistContext(DATA_AXIS)
    takes_seed = "rng_seed" in inspect.signature(grow_fn).parameters

    def step(X_t, grad, hess, in_bag, scores_k, lr, feat_mask, seed, meta):
        kw = dict(feature_mask=feat_mask, dist=dist)
        if takes_seed:
            kw["rng_seed"] = seed
        tree, leaf_of_row = grow_fn(X_t, grad, hess, in_bag, meta, cfg,
                                    **kw)
        from ..ops.histogram import take_leaf_values
        new_scores = scores_k + take_leaf_values(tree.leaf_value * lr,
                                                 leaf_of_row)
        return tree, leaf_of_row, new_scores

    # feature-parallel (replicate_rows): every shard sees ALL rows and
    # works a feature slice inside the grower; outputs are replicated
    row = P() if replicate_rows else P(DATA_AXIS)
    rep = P()
    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=((P() if replicate_rows else P(None, DATA_AXIS)),
                  row, row, row, row, rep, rep, rep, rep),
        out_specs=(rep, row, row),
        check_vma=False)
    return jax.jit(sharded)


def build_sharded_score_fn(mesh: jax.sharding.Mesh, score_fn,
                           extra_row_args: int = 0):
    """jit(shard_map) wrapper for data-parallel SERVING scoring: request
    batches shard over the mesh `data` axis, the model (closed over by
    `score_fn` as pinned device arrays) replicates — the inference-side
    twin of `build_data_parallel_train_fn`, with no collectives at all
    (per-row scoring is embarrassingly parallel; the reference's
    predictor just OMP-parallelizes rows, application/predictor.hpp).

    `score_fn(X [n, F], *extras) -> [K, n]` per shard; the wrapped fn
    takes a batch whose row count divides the data-axis size (pad with
    `pad_rows_to`) and returns the full [K, n] on the host mesh.
    `extra_row_args` extra PER-ROW 1-D operands (e.g. the fused scorer's
    tenant-id vector, export/fusion.py) shard along the same axis.
    """
    sharded = jax.shard_map(
        score_fn, mesh=mesh,
        in_specs=(P(DATA_AXIS, None),) + (P(DATA_AXIS),) * extra_row_args,
        out_specs=P(None, DATA_AXIS),
        check_vma=False)
    return jax.jit(sharded)


def shard_rows(mesh: jax.sharding.Mesh, arr, row_axis: int = 0):
    """Place an array with rows sharded over the mesh data axis."""
    spec = [None] * arr.ndim
    spec[row_axis] = DATA_AXIS
    return jax.device_put(arr, NamedSharding(mesh, P(*spec)))


def replicated(mesh: jax.sharding.Mesh, arr):
    return jax.device_put(arr, NamedSharding(mesh, P()))
