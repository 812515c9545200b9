"""Runtime subsystem: device profiling and kernel/strategy autotuning.

The reference locks in a histogram layout by *measuring* it: at InitTrain,
TrainingShareStates times row-wise vs col-wise histogram construction on
the real data and keeps the faster one (src/io/train_share_states.cpp).
This package is that idea generalized for the TPU build:

 * `profiler`  — per-iteration stage spans with proper device fencing
   (block_until_ready around jitted segments), throughput counters,
   an HBM watermark, a ring buffer, and JSON export consumed by
   bench.py / BENCH_*.json; and the unfenced `span` / `count`
   primitive whose records also land in a profiler trace as `lgbm:*`.
 * `autotune`  — at train init, short timed probes of the candidate
   grower strategies (ops/grow.py / grow_fast.py / grow_wave.py) and
   histogram chunk layouts on a subsample of the real binned matrix;
   the winner is cached in-process and on disk keyed by
   (n_rows, n_features, max_bin, num_leaves, device kind).
 * `checkpoint` — iteration-level deterministic checkpoint/resume:
   atomic snapshot writes with checksummed manifests, bounded
   retention, and bit-identical crash recovery (docs/ROBUSTNESS.md).
 * `faults`    — deterministic fault-injection plans for resilience
   tests (kill/raise/sleep/corrupt_snapshot/fail_collective).

Enabled through config: `device_profile=true` (alias `profile`, CLI
`--profile`), `autotune=true`, `checkpoint_interval>0`. All default
off; `autotune=false` reproduces the hard-coded strategy ladder
bit-for-bit and `checkpoint_interval=0` leaves the training hot path
untouched.

Imports stay lazy/light here: this module must be importable before any
XLA backend is initialized (multi-host bring-up orders
jax.distributed.initialize before the first backend touch).
"""

from .profiler import (StageProfiler, count, set_spans,  # noqa: F401
                       span, spans)
from .autotune import (AUTOTUNE_PREFERENCE, autotune_decision,  # noqa: F401
                       load_disk_cache, make_key, pin_comm_decision,
                       save_disk_cache)
from .checkpoint import (CheckpointError, CheckpointManager,  # noqa: F401
                         atomic_write_bytes, atomic_write_text,
                         capture_trainer_state, load_checkpoint,
                         restore_trainer_state, verify_manifest,
                         write_manifest)
from .faults import (CollectiveFault, FaultPlan,  # noqa: F401
                     InjectedFault, active_plan)
