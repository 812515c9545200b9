"""Resilience suite (docs/ROBUSTNESS.md).

Crash-and-resume bit-identity: training killed mid-run (runtime/faults.py
``kill@iter=k`` — a hard ``os._exit``, so it MUST run in a subprocess)
and resumed from its checkpoint must produce the same model md5 as an
uninterrupted run, serially and on the 8-device virtual data-parallel
mesh, for two checkpoint intervals. The uninterrupted baselines also run
with checkpointing ON. Fault-injected runs are routed through the
per-iteration path (`kill@iter` fires in train_one_iter's watchdog);
clean/resumed runs may take the batched-scan path, whose chunks are
md5-identical to per-iteration training and whose boundaries align to
checkpoint intervals (tests/test_batched.py), so both paths satisfy the
same bit-identity contract.

Plus: corrupt-checkpoint fallback, registry snapshot validation and
watch-state persistence, batcher worker-death delivery, watchdog
degrade, straggler flagging, fault-plan grammar, atomic writes.
"""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.runtime.checkpoint import (CheckpointManager,
                                             atomic_write_text,
                                             verify_manifest,
                                             write_manifest)
from lightgbm_tpu.runtime.faults import (FaultPlan, InjectedFault,
                                         corrupt_file)
from lightgbm_tpu.utils.log import FatalError

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one deterministic shape shared by every training in this module: the
# subprocess workers regenerate it from the same seed
N_ROWS, N_COLS, N_ROUNDS, KILL_AT = 320, 8, 12, 7
BASE_PARAMS = dict(objective="binary", num_leaves=7, min_data_in_leaf=5,
                   learning_rate=0.2, bagging_freq=3, bagging_fraction=0.7,
                   feature_fraction=0.8, seed=3, verbose=-1,
                   deterministic=True)


def _data():
    rng = np.random.RandomState(0)
    X = rng.normal(size=(N_ROWS, N_COLS)).astype(np.float32)
    y = (X[:, 0] + 0.5 * rng.normal(size=N_ROWS) > 0).astype(np.float32)
    return X, y


_WORKER = """\
import hashlib, json, sys
spec = json.load(open(sys.argv[1]))
import numpy as np
import lightgbm_tpu as lgb
rng = np.random.RandomState(0)
X = rng.normal(size=({n}, {c})).astype(np.float32)
y = (X[:, 0] + 0.5 * rng.normal(size={n}) > 0).astype(np.float32)
b = lgb.train(spec["params"], lgb.Dataset(X, label=y),
              num_boost_round=spec["rounds"])
text = b.model_to_string()
with open(spec["out"], "w") as f:
    json.dump({{"md5": hashlib.md5(text.encode()).hexdigest()}}, f)
""".format(n=N_ROWS, c=N_COLS)


def _spawn(tmp_path, tag, params, env, rounds=N_ROUNDS):
    """Launch one training subprocess; returns (Popen, result_path)."""
    worker = tmp_path / "worker.py"
    if not worker.exists():
        worker.write_text(_WORKER)
    spec_path = tmp_path / f"spec_{tag}.json"
    out_path = tmp_path / f"out_{tag}.json"
    spec_path.write_text(json.dumps(
        {"params": params, "rounds": rounds, "out": str(out_path)}))
    proc = subprocess.Popen(
        [sys.executable, str(worker), str(spec_path)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    return proc, out_path


def _finish(proc, out_path, expect_rc):
    stdout, _ = proc.communicate(timeout=600)
    assert proc.returncode == expect_rc, \
        f"expected rc={expect_rc}, got {proc.returncode}: " + stdout[-3000:]
    if expect_rc == 0:
        with open(out_path) as f:
            return json.load(f)["md5"]
    return None


def _crash_resume_case(tmp_path, extra_params, env, intervals):
    """The full crash/resume matrix for one device layout: a
    checkpointed uninterrupted baseline, then per interval a killed run
    (rc 17 from the kill directive) and a resume, all md5-compared.
    Independent subprocesses run concurrently to bound wall time."""
    base = dict(BASE_PARAMS, **extra_params)

    wave1 = [_spawn(tmp_path, "baseline",
                    dict(base, checkpoint_interval=intervals[0],
                         checkpoint_dir=str(tmp_path / "base_ckpt")),
                    env)]
    for iv in intervals:
        wave1.append(_spawn(
            tmp_path, f"kill_{iv}",
            dict(base, checkpoint_interval=iv,
                 checkpoint_dir=str(tmp_path / f"ckpt_{iv}"),
                 fault_plan=f"kill@iter={KILL_AT}"),
            env))
    baseline_md5 = _finish(*wave1[0], expect_rc=0)
    for proc_out in wave1[1:]:
        _finish(*proc_out, expect_rc=17)

    wave2 = []
    for iv in intervals:
        ckpt_dir = tmp_path / f"ckpt_{iv}"
        # the kill really left a mid-run checkpoint behind
        assert CheckpointManager(str(ckpt_dir)).checkpoints(), \
            f"no checkpoint written before the kill (interval {iv})"
        wave2.append((iv, _spawn(
            tmp_path, f"resume_{iv}",
            dict(base, checkpoint_interval=iv,
                 checkpoint_dir=str(tmp_path / f"resume_ckpt_{iv}"),
                 resume_from_checkpoint=str(ckpt_dir)),
            env)))
    for iv, proc_out in wave2:
        md5 = _finish(*proc_out, expect_rc=0)
        assert md5 == baseline_md5, \
            f"resumed model differs from uninterrupted (interval {iv})"


def test_crash_resume_bit_identical_serial(tmp_path):
    env = dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("LIGHTGBM_TPU_FAULT_PLAN", None)
    _crash_resume_case(tmp_path, {}, env, intervals=(4, 5))


def test_crash_resume_bit_identical_data_parallel_mesh(tmp_path):
    env = dict(
        os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.pop("LIGHTGBM_TPU_FAULT_PLAN", None)
    _crash_resume_case(tmp_path, {"tree_learner": "data"}, env,
                       intervals=(4, 5))


def test_corrupt_checkpoint_falls_back(tmp_path):
    """A checkpoint corrupted after its write (injected torn buffer)
    fails its manifest checksum; resume skips it, falls back to the
    previous snapshot, and still reaches the bit-identical model."""
    X, y = _data()
    d_faulty = str(tmp_path / "faulty")
    params = dict(BASE_PARAMS, checkpoint_interval=4,
                  checkpoint_dir=d_faulty,
                  fault_plan="corrupt_snapshot@iter=8")
    lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=10)

    mgr = CheckpointManager(d_faulty)
    iters = [it for it, _ in mgr.checkpoints()]
    assert 8 in iters and 4 in iters
    ok, reason = verify_manifest(mgr.path_for(8))
    assert not ok and "sha256" in reason
    state = mgr.load_latest()
    assert state is not None and state["iteration"] == 4

    baseline = lgb.train(
        dict(BASE_PARAMS, checkpoint_interval=4,
             checkpoint_dir=str(tmp_path / "base")),
        lgb.Dataset(X, label=y), num_boost_round=N_ROUNDS)
    resumed = lgb.train(
        dict(BASE_PARAMS, checkpoint_interval=4,
             checkpoint_dir=str(tmp_path / "resumed"),
             resume_from_checkpoint=d_faulty),
        lgb.Dataset(X, label=y), num_boost_round=N_ROUNDS)
    assert resumed.model_to_string() == baseline.model_to_string()


def test_checkpoint_retention_bounded(tmp_path):
    X, y = _data()
    d = str(tmp_path / "ckpt")
    lgb.train(dict(BASE_PARAMS, checkpoint_interval=2, checkpoint_dir=d,
                   checkpoint_retention=2),
              lgb.Dataset(X, label=y), num_boost_round=N_ROUNDS)
    iters = [it for it, _ in CheckpointManager(d).checkpoints()]
    assert iters == [10, 12]
    # manifests pruned alongside
    assert len([f for f in os.listdir(d) if f.endswith(".manifest.json")]) \
        == 2


# ---------------------------------------------------------------------------
# registry publish-path hardening


def _make_model():
    X, y = _data()
    return lgb.train(dict(BASE_PARAMS), lgb.Dataset(X, label=y),
                     num_boost_round=3)


def _registry():
    from lightgbm_tpu.serving import ModelRegistry
    return ModelRegistry(engine="host", warmup=False)


def test_registry_rejects_truncated_and_corrupt_snapshots(tmp_path):
    booster = _make_model()
    prefix = str(tmp_path / "model.txt")
    booster.save_model(prefix)

    reg = _registry()
    reg.register("m", prefix)
    reg.watch_snapshots("m", prefix, start=False)
    v0 = reg.session("m").version

    # valid snapshot promotes
    booster.save_model(f"{prefix}.snapshot_iter_5.txt")
    assert reg.poll_snapshots("m") == 5
    assert reg.session("m").version == v0 + 1

    # truncated snapshot (no end-of-parameters marker): rejected, the
    # promoted session keeps serving
    with open(f"{prefix}.snapshot_iter_6.txt", "w") as f:
        f.write(booster.model_to_string()[:200])
    assert reg.poll_snapshots("m") is None
    assert reg.session("m").version == v0 + 1
    assert reg.metrics.counters.get("snapshots_rejected") == 1

    # checksum-failing snapshot (manifest present, bytes corrupted
    # without changing the size): rejected the same way
    p7 = f"{prefix}.snapshot_iter_7.txt"
    booster.save_model(p7)
    write_manifest(p7)
    corrupt_file(p7)
    assert reg.poll_snapshots("m") is None
    assert reg.session("m").version == v0 + 1

    # a later valid snapshot still gets through
    p8 = f"{prefix}.snapshot_iter_8.txt"
    booster.save_model(p8)
    write_manifest(p8)
    assert reg.poll_snapshots("m") == 8
    assert reg.session("m").version == v0 + 2


def test_registry_watch_state_survives_restart(tmp_path):
    booster = _make_model()
    prefix = str(tmp_path / "model.txt")
    booster.save_model(prefix)
    booster.save_model(f"{prefix}.snapshot_iter_5.txt")

    reg = _registry()
    reg.register("m", prefix)
    reg.watch_snapshots("m", prefix, start=False)
    assert reg.poll_snapshots("m") == 5
    assert os.path.exists(prefix + ".watch_state.json")

    # "restarted" serve process: a fresh registry on the same prefix
    # must not re-promote the snapshot it already served
    reg2 = _registry()
    reg2.register("m", prefix)
    reg2.watch_snapshots("m", prefix, start=False)
    v = reg2.session("m").version
    assert reg2.poll_snapshots("m") is None
    assert reg2.session("m").version == v
    assert reg2.metrics.counters["swaps"] == 0

    # initial_iter floor (cli run_serve passes the booted snapshot's
    # iteration) wins over a missing/behind state file
    reg3 = _registry()
    reg3.register("m", f"{prefix}.snapshot_iter_5.txt")
    reg3.watch_snapshots("m", prefix, start=False, initial_iter=9,
                         state_file=str(tmp_path / "fresh_state.json"))
    assert reg3.poll_snapshots("m") is None


# ---------------------------------------------------------------------------
# batcher worker death


def test_batcher_worker_death_fails_fast():
    import threading

    from lightgbm_tpu.serving.batcher import MicroBatcher

    release = threading.Event()

    def predict_fn(X):
        release.wait(5.0)
        return np.zeros(X.shape[0])

    b = MicroBatcher(predict_fn, max_batch=4, max_wait_ms=1.0,
                     timeout_ms=10_000.0)
    b.start()
    r1 = b.submit(np.zeros((4, 2)))   # fills max_batch -> scored alone
    r2 = b.submit(np.zeros((4, 2)))   # queued behind it

    # anything escaping the per-batch guard (here: the gather path
    # itself breaking) must kill the worker LOUDLY; predict_fn is still
    # parked on `release`, so the worker can't re-enter _gather before
    # the patch lands
    def broken_gather():
        raise RuntimeError("boom in gather")

    b._gather = broken_gather
    release.set()
    assert b.wait(r1, timeout=5.0).shape == (4,)
    # the queued request is failed with the worker-death diagnosis
    # instead of stranding its caller until timeout
    with pytest.raises(RuntimeError, match="worker died"):
        b.wait(r2, timeout=5.0)
    # subsequent submits fail fast naming the original cause
    with pytest.raises(RuntimeError, match="boom in gather"):
        b.submit(np.zeros((1, 2)))
    assert b._running is False


def test_batcher_per_batch_errors_do_not_kill_worker():
    from lightgbm_tpu.serving.batcher import MicroBatcher

    calls = {"n": 0}

    def predict_fn(X):
        calls["n"] += 1
        if calls["n"] == 1:
            raise ValueError("bad batch")
        return np.zeros(X.shape[0])

    with MicroBatcher(predict_fn, max_wait_ms=0.1) as b:
        with pytest.raises(ValueError):
            b.predict(np.zeros((4, 2)))
        assert b.predict(np.zeros((4, 2))).shape == (4,)
        assert b._fatal is None


# ---------------------------------------------------------------------------
# watchdog, stragglers, fault grammar, atomic writes


def test_watchdog_degrades_to_allreduce_and_pins(tmp_path):
    if len(__import__("jax").devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    X, y = _data()
    cache = str(tmp_path / "autotune.json")
    params = dict(BASE_PARAMS, tree_learner="data",
                  parallel_hist_mode="reduce_scatter",
                  fault_plan="fail_collective@iter=2:times=2",
                  autotune_cache=cache)
    booster = lgb.train(params, lgb.Dataset(X, label=y),
                        num_boost_round=6)
    g = booster._gbdt
    assert g.iter == 6                      # training completed
    assert g.grow_cfg.parallel_hist_mode == "allreduce"
    assert g._collective_failures == 2
    assert g.autotune_decision["pinned"] is True
    with open(cache) as f:
        disk = json.load(f)
    assert any(v.get("pinned") and v.get("parallel_hist_mode")
               == "allreduce" for v in disk.values())


def test_straggler_flagged_from_span_skew():
    from lightgbm_tpu.runtime.profiler import StageProfiler

    prof = StageProfiler(barrier=lambda: None)
    for _ in range(6):   # rank 2 persistently ~3x the median
        prof.record_rank_spans("grow", [0.010, 0.011, 0.031, 0.010])
    report = prof.to_dict()["stragglers"]["grow"]
    assert report["straggler_ranks"] == [2]
    assert report["skew"] > 2.5
    # threshold is honored: at 4x nothing is flagged
    prof.straggler_threshold = 4.0
    assert prof.straggler_report()["grow"]["straggler_ranks"] == []


def test_fault_plan_grammar():
    plan = FaultPlan.parse(
        "kill@iter=7; raise@iter=3:times=2, sleep@iter=2:rank=1:ms=5;"
        "corrupt_snapshot@iter=8 ; fail_collective@iter=2:times=3")
    assert len(plan.directives) == 5
    with pytest.raises(InjectedFault):
        plan.at_iteration(3)
    with pytest.raises(InjectedFault):
        plan.at_iteration(3)
    plan.at_iteration(3)                      # times=2 exhausted
    plan.at_iteration(0)                      # nothing pinned there
    assert plan.should_corrupt_snapshot(8) is True
    assert plan.should_corrupt_snapshot(8) is False   # consumed once
    assert FaultPlan.parse("") is None and FaultPlan.parse("  ") is None
    with pytest.raises(ValueError, match="unknown fault action"):
        FaultPlan.parse("explode@iter=1")


def test_config_validation_and_env_plan(monkeypatch):
    from lightgbm_tpu.config import resolve_params
    from lightgbm_tpu.runtime.faults import active_plan

    with pytest.raises(FatalError):
        resolve_params({"checkpoint_interval": 5})    # no checkpoint_dir
    with pytest.raises(FatalError):
        resolve_params({"checkpoint_interval": -1})
    cfg = resolve_params({"checkpoint_freq": 5, "ckpt_dir": "/tmp/x",
                          "resume": "/tmp/y"})
    assert cfg.checkpoint_interval == 5
    assert cfg.checkpoint_dir == "/tmp/x"
    assert cfg.resume_from_checkpoint == "/tmp/y"
    assert active_plan("") is None
    monkeypatch.setenv("LIGHTGBM_TPU_FAULT_PLAN", "raise@iter=1")
    assert active_plan("").spec == "raise@iter=1"
    assert active_plan("kill@iter=2").spec == "kill@iter=2"


def test_atomic_write_and_manifest_roundtrip(tmp_path):
    path = str(tmp_path / "f.txt")
    atomic_write_text(path, "hello world\n")
    assert open(path).read() == "hello world\n"
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    write_manifest(path)
    assert verify_manifest(path) == (True, "ok")
    corrupt_file(path)
    ok, reason = verify_manifest(path)
    assert not ok and "sha256" in reason
    assert verify_manifest(str(tmp_path / "nope"))[0] is False


def test_save_model_has_no_orchestration_params(tmp_path):
    """The model-file parameter echo must not leak run-orchestration
    state (resume paths differ between a killed+resumed run and its
    baseline, and md5 equality is the contract)."""
    X, y = _data()
    b = lgb.train(dict(BASE_PARAMS, checkpoint_interval=4,
                       checkpoint_dir=str(tmp_path / "c")),
                  lgb.Dataset(X, label=y), num_boost_round=3)
    text = b.model_to_string()
    for knob in ("checkpoint_dir", "resume_from_checkpoint", "fault_plan"):
        assert knob not in text


def test_checkpoint_pinning_a_removed_histogram_impl_is_refused():
    """A checkpoint pins the saving run's histogram_impl as a plain
    string. One written where histogram_impl=fused still existed must be
    refused on resume, not handed to the grower."""
    from lightgbm_tpu.runtime.checkpoint import (capture_trainer_state,
                                                 restore_trainer_state)
    from lightgbm_tpu.utils.log import FatalError
    X, y = _data()
    gbdt = lgb.train(dict(BASE_PARAMS), lgb.Dataset(X, label=y),
                     num_boost_round=2)._gbdt
    state = capture_trainer_state(gbdt)
    restore_trainer_state(gbdt, state)            # its own state: taken
    state["grow_pins"]["hist_impl"] = "fused"
    with pytest.raises(FatalError, match="histogram_impl='fused'"):
        restore_trainer_state(gbdt, state)
