"""Test configuration: force an 8-device virtual CPU mesh.

Must set XLA flags before jax is imported anywhere (the driver's
dryrun_multichip uses the same mechanism to validate multi-chip sharding
without real chips).
"""

import os

# force CPU even when the shell presets JAX_PLATFORMS: tests need the
# virtual 8-device mesh and deterministic fast compiles.
# LIGHTGBM_TPU_TEST_ON_TPU=1 opts out for the hardware-gated parity suite
# (tests/test_tpu_parity.py).
_ON_TPU = os.environ.get("LIGHTGBM_TPU_TEST_ON_TPU", "") == "1"
if not _ON_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import threading  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Batched scan training (the library default) compiles one extra scan
# executable per Booster; across the suite's hundreds of tiny train()
# calls that is minutes of pure XLA compile time for paths that are
# md5-identical to the per-iteration loop anyway. Tier-1 therefore runs
# the per-iteration path by default; tests/test_batched.py opts back in
# per-test (monkeypatch) and owns batched coverage. An explicit value
# in the environment (e.g. "0" to force batched everywhere) wins.
os.environ.setdefault("LIGHTGBM_TPU_DISABLE_BATCHED", "1")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running test, excluded from tier-1 (-m 'not slow')")


@pytest.fixture
def rng():
    return np.random.RandomState(42)


@pytest.fixture
def force_row_blocks(monkeypatch):
    """``force_row_blocks(tiles=1, in_flight=2)`` replaces the device
    predictor's block rule (models/predictor.py _row_blocks, which has no
    knob: it reads the table's bytes) by blocks of ``tiles`` row tiles,
    so that a table the CPU holds makes several."""
    from lightgbm_tpu.models import predictor

    def force(tiles=1, in_flight=2):
        def rule(rows, features, row_tile):
            blocks = -(-rows // (tiles * row_tile))
            if blocks <= 1:
                return rows, 1, 1
            return tiles * row_tile, blocks, min(blocks, in_flight)

        monkeypatch.setattr(predictor, "_row_blocks", rule)

    return force


@pytest.fixture(autouse=True)
def no_leaked_threads():
    """Fail any test that leaves a NON-DAEMON thread running: a leaked
    worker would hang interpreter shutdown (daemon threads — the serving
    batcher, snapshot watchers, ThreadingHTTPServer handlers — are
    allowed but are expected to be stopped by the test itself). Fleet
    scheduler workers ("serving-fleet*") and fused-supertensor rebuild
    threads ("fleet-fused*", serving/fleet.py) are daemons but held to
    the same standard: a leaked one keeps scoring tenants (or compiling
    supertensors) across tests, so it fails the test too — as is the
    batched-training async tree drain ("gbdt-tree-drain",
    models/gbdt.py), which engine.py must stop_drain() on every exit
    path."""
    before = {t.ident for t in threading.enumerate()}
    yield
    fresh = [t for t in threading.enumerate()
             if t.ident not in before and t.is_alive()]
    leaked = [t for t in fresh
              if not t.daemon
              or t.name.startswith(("serving-fleet", "fleet-fused",
                                    "gbdt-tree-drain"))]
    if leaked:
        # give naturally-finishing threads a grace period before failing
        deadline = 2.0 / max(len(leaked), 1)
        for t in leaked:
            t.join(timeout=deadline)
        leaked = [t for t in leaked if t.is_alive()]
    assert not leaked, (
        f"test leaked thread(s): {[t.name for t in leaked]}")
