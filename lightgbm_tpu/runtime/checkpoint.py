"""Iteration-level deterministic checkpoint / resume.

A checkpoint captures the FULL trainer state at an iteration boundary —
host trees, the exact f32 score matrix, bagging/feature-mask RNG
position (re-derivable: every sampler is keyed by ``seed + iteration``),
objective identity, autotune pins and the per-rank comm mode — so a run
killed at iteration k and resumed produces bit-identical final model
bytes to an uninterrupted run (tests/test_resilience.py asserts md5
equality, serial and on the 8-device mesh).

On-disk layout (``docs/ROBUSTNESS.md``):

    <dir>/ckpt_iter_0000010.pkl                pickled state dict
    <dir>/ckpt_iter_0000010.pkl.manifest.json  {"sha256", "bytes", ...}

Every write is atomic (same-dir temp -> flush -> fsync -> os.replace)
and the manifest is written LAST, from the in-memory payload hash: a
torn or corrupted payload fails its checksum and the loader falls back
to the next-older checkpoint. Retention is bounded (newest N kept).

The manager is state-shape agnostic: the online loop persists its own
state dicts through the same machinery (``kind="online_loop"`` — anchor
model, window arrays, policy counters, publish seq; online/trainer.py),
keyed by publish seq instead of boosting iteration, with the same
guarantee (a killed loop resumes to md5-identical published snapshots,
docs/ONLINE.md). Loaders that share a ``checkpoint_dir`` across both
uses tell the states apart by their ``kind`` field.

This module is imported eagerly by ``runtime/__init__`` so it must stay
stdlib+numpy at the top level; jax and the model classes are imported
inside functions.
"""

import hashlib
import json
import os
import pickle
import re
import tempfile
from typing import Any, Dict, List, Optional, Tuple

from ..config import HISTOGRAM_IMPLS
from ..utils.log import log_fatal, log_info, log_warning

STATE_FORMAT = 1
_CKPT_RE = re.compile(r"ckpt_iter_(\d+)\.pkl$")


class CheckpointError(RuntimeError):
    """A checkpoint file is missing, truncated, or fails its checksum."""


# ---------------------------------------------------------------------------
# atomic writes + checksum manifests (shared with Booster.save_model and
# the cli snapshot callback — satellite: no reader may ever observe a
# half-written model file)

def _atomic_write(path: str, data: bytes, mode: str = "wb") -> None:
    d = os.path.dirname(os.path.abspath(path))
    fd, tmp = tempfile.mkstemp(dir=d, prefix=os.path.basename(path) + ".",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def atomic_write_bytes(path: str, data: bytes) -> None:
    """write-temp -> fsync -> rename; the destination either holds the
    old content or the complete new content, never a prefix."""
    _atomic_write(path, data)


def atomic_write_text(path: str, text: str) -> None:
    _atomic_write(path, text.encode("utf-8"))


def manifest_path(path: str) -> str:
    return path + ".manifest.json"


def _write_manifest_for_bytes(path: str, payload: bytes,
                              extra: Optional[Dict[str, Any]] = None) -> None:
    manifest = {"sha256": hashlib.sha256(payload).hexdigest(),
                "bytes": len(payload)}
    if extra:
        manifest.update(extra)
    atomic_write_text(manifest_path(path),
                      json.dumps(manifest, indent=2, sort_keys=True))


def write_manifest(path: str,
                   extra: Optional[Dict[str, Any]] = None) -> None:
    """Sidecar checksum for an already-written file (model snapshots);
    consumers (serving/registry.py) verify before promoting."""
    with open(path, "rb") as f:
        _write_manifest_for_bytes(path, f.read(), extra)


def verify_manifest(path: str) -> Tuple[bool, str]:
    """(ok, reason). Fails on missing/unreadable manifest, size
    mismatch (truncation) or checksum mismatch (corruption)."""
    mpath = manifest_path(path)
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except FileNotFoundError:
        return False, "missing manifest"
    except Exception as e:
        return False, f"unreadable manifest: {e!r}"
    try:
        with open(path, "rb") as f:
            payload = f.read()
    except Exception as e:
        return False, f"unreadable payload: {e!r}"
    if len(payload) != int(manifest.get("bytes", -1)):
        return False, (f"size mismatch: {len(payload)} != "
                       f"{manifest.get('bytes')} (truncated?)")
    if hashlib.sha256(payload).hexdigest() != manifest.get("sha256"):
        return False, "sha256 mismatch (corrupted)"
    return True, "ok"


# ---------------------------------------------------------------------------
# checkpoint store

class CheckpointManager:
    """Bounded store of ``ckpt_iter_*.pkl`` snapshots in one directory.

    ``fault_plan`` is the test-only hook that corrupts a just-written
    payload (runtime/faults.py ``corrupt_snapshot`` directive); the
    manifest hash is computed from the in-memory payload, so the
    corruption is detected at load time and the loader falls back."""

    def __init__(self, directory: str, retention: int = 3,
                 fault_plan: Optional[Any] = None):
        if not directory:
            log_fatal("CheckpointManager needs a checkpoint_dir")
        self.directory = directory
        self.retention = max(int(retention), 1)
        self.fault_plan = fault_plan

    def path_for(self, iteration: int) -> str:
        return os.path.join(self.directory,
                            f"ckpt_iter_{int(iteration):07d}.pkl")

    def checkpoints(self) -> List[Tuple[int, str]]:
        """(iteration, path) ascending by iteration."""
        out = []
        try:
            names = os.listdir(self.directory)
        except OSError:
            return []
        for name in names:
            m = _CKPT_RE.search(name)
            if m:
                out.append((int(m.group(1)),
                            os.path.join(self.directory, name)))
        return sorted(out)

    def save(self, state: Dict[str, Any], iteration: int) -> str:
        os.makedirs(self.directory, exist_ok=True)
        path = self.path_for(iteration)
        payload = pickle.dumps(state, protocol=4)
        atomic_write_bytes(path, payload)
        if self.fault_plan is not None and \
                self.fault_plan.should_corrupt_snapshot(iteration):
            from .faults import corrupt_file
            corrupt_file(path)
        # manifest hash comes from the in-memory payload, not a re-read:
        # anything that mangles the file after the write (injected or
        # real) fails verification at load time
        _write_manifest_for_bytes(path, payload,
                                  {"iteration": int(iteration),
                                   "format": STATE_FORMAT})
        self._prune()
        return path

    def _prune(self) -> None:
        for _, path in self.checkpoints()[:-self.retention]:
            for p in (path, manifest_path(path)):
                try:
                    os.unlink(p)
                except OSError:
                    pass

    def load(self, path: str) -> Dict[str, Any]:
        ok, reason = verify_manifest(path)
        if not ok:
            raise CheckpointError(f"checkpoint {path} rejected: {reason}")
        with open(path, "rb") as f:
            state = pickle.load(f)
        if int(state.get("format", 0)) != STATE_FORMAT:
            raise CheckpointError(
                f"checkpoint {path} has format {state.get('format')}, "
                f"this build reads format {STATE_FORMAT}")
        return state

    def load_latest(self) -> Optional[Dict[str, Any]]:
        """Newest checkpoint that passes verification; corrupt ones are
        skipped with a warning (the bounded-retention ladder is the
        recovery path for a fault during the checkpoint write itself)."""
        for it, path in reversed(self.checkpoints()):
            try:
                return self.load(path)
            except (CheckpointError, pickle.UnpicklingError,
                    EOFError) as e:
                log_warning(f"skipping checkpoint at iteration {it}: {e}")
        return None


def load_checkpoint(path: str) -> Dict[str, Any]:
    """``resume_from_checkpoint`` accepts a checkpoint file or a
    checkpoint directory (newest valid snapshot wins)."""
    if os.path.isdir(path):
        state = CheckpointManager(path).load_latest()
        if state is None:
            log_fatal(f"no valid checkpoint found under {path}")
        return state
    if not os.path.exists(path):
        log_fatal(f"resume_from_checkpoint: {path} does not exist")
    return CheckpointManager(os.path.dirname(path) or ".").load(path)


# ---------------------------------------------------------------------------
# trainer state capture / restore

def capture_trainer_state(gbdt, best_iteration: int = -1) -> Dict[str, Any]:
    """Snapshot the live trainer. Host trees are materialized first
    (``_device_tree_to_host`` is deterministic, so capturing them here
    is bit-identical to capturing at the end of training); scores are
    the exact f32 device bytes."""
    import jax
    import numpy as np

    from ..models.gbdt import GBDT

    if type(gbdt) is not GBDT:
        log_fatal("checkpointing supports boosting=gbdt only (DART/RF "
                  "carry per-iteration drop state that is not captured; "
                  "docs/ROBUSTNESS.md escape hatches)")
    if getattr(gbdt, "_pre_part", False):
        log_fatal("checkpointing is not supported with pre-partitioned "
                  "multi-host datasets yet (per-rank shards would need "
                  "per-rank snapshots; docs/ROBUSTNESS.md)")
    gbdt._materialize_models()
    return {
        "format": STATE_FORMAT,
        "iteration": int(gbdt.iter),
        "stopped": bool(gbdt._stopped),
        "best_iteration": int(best_iteration),
        "num_data": int(gbdt.num_data),
        "num_class": int(gbdt.num_class),
        "num_tree_per_iteration": int(gbdt.num_tree_per_iteration),
        "objective": (gbdt.objective.to_string()
                      if gbdt.objective is not None else ""),
        "shrinkage_rate": float(gbdt.shrinkage_rate),
        "models": list(gbdt._models),
        "scores": np.asarray(jax.device_get(gbdt.scores), np.float32),
        "valid_scores": [np.asarray(jax.device_get(v), np.float32)
                         for v in gbdt._valid_scores],
        "cegb_used": (np.asarray(jax.device_get(gbdt._cegb_used))
                      if getattr(gbdt, "_cegb_used", None) is not None
                      else None),
        "grower": str(gbdt.grower),
        "grow_pins": {
            "rows_per_chunk": int(gbdt.grow_cfg.rows_per_chunk),
            "hist_impl": str(gbdt.grow_cfg.hist_impl),
            "parallel_hist_mode": str(gbdt.grow_cfg.parallel_hist_mode),
        },
        "autotune_decision": gbdt.autotune_decision,
        "mesh_size": int(getattr(gbdt, "n_shards", 1)),
    }


def restore_trainer_state(gbdt, state: Dict[str, Any]) -> None:
    """Rebuild a freshly-initialized trainer to the exact save point.

    Deterministic-resume contract (docs/ROBUSTNESS.md):
      * scores are restored byte-for-byte (padding is stripped and
        re-applied for the CURRENT mesh — pad rows never reach
        histograms, their in_bag weight is 0 — so a serial checkpoint
        resumes on a mesh and vice versa);
      * autotune choices are PINNED from the checkpoint, never
        re-probed (probes are timing-dependent and could flip the
        kernel choice mid-model);
      * the in-bag mask live at the save point is re-derived from its
        iteration key (device strategies fold the floored iteration
        ``floor(iter / period) * period`` into their PRNG key; host
        strategies seed numpy with ``bagging_seed + floored_iter``) —
        sampling is a pure function of the iteration, so restore needs
        no carried mask state.
    """
    import jax.numpy as jnp
    import numpy as np

    from ..models.gbdt import GBDT

    if type(gbdt) is not GBDT:
        log_fatal("resume_from_checkpoint supports boosting=gbdt only")
    if getattr(gbdt, "_pre_part", False):
        log_fatal("resume_from_checkpoint is not supported with "
                  "pre-partitioned multi-host datasets yet")
    for key in ("num_data", "num_class", "num_tree_per_iteration"):
        if int(state[key]) != int(getattr(gbdt, key)):
            log_fatal(f"checkpoint {key}={state[key]} does not match the "
                      f"training set ({getattr(gbdt, key)}); resume needs "
                      "the identical dataset and params")
    obj = gbdt.objective.to_string() if gbdt.objective is not None else ""
    if str(state.get("objective", "")) != obj:
        log_fatal(f"checkpoint objective {state.get('objective')!r} does "
                  f"not match configured objective {obj!r}")

    gbdt._models = list(state["models"])
    gbdt._pending = []
    gbdt.iter = int(state["iteration"])
    gbdt._stopped = bool(state["stopped"])
    gbdt.shrinkage_rate = float(state["shrinkage_rate"])

    scores = np.asarray(state["scores"], np.float32)[:, :gbdt.num_data]
    if gbdt._host_pad != gbdt.num_data:
        scores = np.pad(scores,
                        ((0, 0), (0, gbdt._host_pad - gbdt.num_data)))
    gbdt.scores = gbdt._put_rows(jnp.asarray(scores), row_axis=1)

    vs = state.get("valid_scores") or []
    if gbdt._valid_scores:
        if len(vs) == len(gbdt._valid_scores):
            gbdt._valid_scores = [jnp.asarray(np.asarray(v, np.float32))
                                  for v in vs]
        else:
            log_warning(f"checkpoint holds {len(vs)} valid-score sets but "
                        f"{len(gbdt._valid_scores)} valid sets are "
                        "registered; keeping replayed valid scores")

    cegb = state.get("cegb_used")
    if cegb is not None and getattr(gbdt, "_cegb_used", None) is not None:
        gbdt._cegb_used = jnp.asarray(np.asarray(cegb))

    rebuild = False
    saved_grower = str(state.get("grower") or "")
    if saved_grower and saved_grower != gbdt.grower:
        gbdt.grower = saved_grower
        rebuild = True
    pins = state.get("grow_pins") or {}
    if pins.get("hist_impl", "auto") not in HISTOGRAM_IMPLS:
        # a checkpoint is input from outside the program: a value this
        # version does not have is refused, never handed to the grower
        log_fatal(f"checkpoint pins histogram_impl='{pins['hist_impl']}', "
                  "which this version does not have (supported: "
                  f"{', '.join(map(repr, HISTOGRAM_IMPLS))}); resume it with the "
                  "version that wrote it, or retrain")
    rep = {k: pins[k] for k in ("rows_per_chunk", "hist_impl",
                                "parallel_hist_mode")
           if k in pins and pins[k] != getattr(gbdt.grow_cfg, k)}
    if rep:
        gbdt.grow_cfg = gbdt.grow_cfg._replace(**rep)
        rebuild = True
    if state.get("autotune_decision") is not None:
        gbdt.autotune_decision = state["autotune_decision"]
    if rebuild:
        gbdt._comm_profile = gbdt._comm_iter_profile()
        gbdt._build_jit_fns()

    strat = gbdt.sample_strategy
    if strat.resample_period() > 0 and not strat.needs_grad \
            and gbdt.iter > 0:
        # re-derive the in-bag mask live at the save point purely from
        # the iteration number (sample() floors it to the last resample
        # iteration internally) — bit-identical to the mask the saving
        # run held, whether it trained per-iteration or in batched
        # chunks (chunk edges align to checkpoint intervals, engine.py).
        # Gradient-keyed strategies (GOSS) re-derive on the next
        # boost anyway (resample_period == 1).
        in_bag = strat.sample(gbdt.iter, None, None)
        if gbdt._host_pad != gbdt.num_data:
            in_bag = jnp.pad(
                in_bag, (0, int(gbdt._host_pad - gbdt.num_data)))
        gbdt._in_bag_dev = gbdt._put_rows(in_bag)

    log_info(f"resumed from checkpoint at iteration {gbdt.iter}"
             + (f" (saved on a {state.get('mesh_size')}-shard mesh, now "
                f"{getattr(gbdt, 'n_shards', 1)})"
                if int(state.get("mesh_size", 1)) !=
                int(getattr(gbdt, "n_shards", 1)) else ""))
