"""Distributed / multi-device layer (reference: src/network/ + the parallel
tree learners, re-expressed as XLA collectives over a jax.sharding.Mesh)."""

from .context import DATA_AXIS, FEATURE_AXIS, DistContext, make_data_mesh
from .data_parallel import (build_data_parallel_train_fn,
                            build_sharded_score_fn, lane_multiple,
                            pad_rows_to,
                            replicated, shard_rows)
from .distributed import init_distributed

# error-message fragments that mark a failed collective (XLA surfaces
# these as generic RuntimeError/XlaRuntimeError; the substrings are the
# only portable signal). The training watchdog uses this to decide
# between a plain retry and the histogram-exchange degrade ladder
# (models/gbdt.py _grow_step, docs/ROBUSTNESS.md).
COLLECTIVE_ERROR_MARKERS = ("collective", "all-reduce", "allreduce",
                            "all-gather", "allgather", "reduce-scatter",
                            "reduce_scatter", "psum", "ppermute",
                            "nccl", "megascale")


def is_collective_error(exc: BaseException) -> bool:
    """True when `exc` looks like a failed cross-device collective
    (injected CollectiveFault or an XLA RUNTIME error naming one).

    A trace or Pallas-lowering error is a plain Python exception and a
    Mosaic compile error names a kernel, not a collective: neither may
    degrade the exchange, whatever its text mentions — the program that
    failed to build would fail the same way under any comm mode."""
    import jax

    from ..runtime.faults import CollectiveFault
    if isinstance(exc, CollectiveFault):
        return True
    if not isinstance(exc, jax.errors.JaxRuntimeError):
        return False
    msg = str(exc).lower()
    if "mosaic" in msg or "tpu_custom_call" in msg:
        return False
    return any(m in msg for m in COLLECTIVE_ERROR_MARKERS)


__all__ = [
    "DATA_AXIS", "FEATURE_AXIS", "DistContext", "make_data_mesh",
    "build_data_parallel_train_fn", "build_sharded_score_fn",
    "lane_multiple", "pad_rows_to", "shard_rows", "replicated",
    "init_distributed", "COLLECTIVE_ERROR_MARKERS", "is_collective_error",
]
