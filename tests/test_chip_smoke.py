"""chip_smoke.py's control flow at toy size on the CPU mesh, and its
refusal to run without a chip (the script itself is the on-chip proof;
this keeps a typo from costing chip time)."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TOY = chip_smoke.Sizes(rows=2000, cols=6, leaves=4, iters=2, max_bins=(15,),
                       predict_rows=300, serve_sizes=(1, 5, 16),
                       serve_max_batch=16, multichip=False)
# what a CPU backend can show: host binning, no Mosaic calls, host predict
CPU = chip_smoke.Expect(binned_on="host", custom_calls=False,
                        device_predict=False, auc_floor=((15, 0.8),))


def test_phases_at_toy_size(monkeypatch, capsys):
    monkeypatch.delenv("LIGHTGBM_TPU_DISABLE_BATCHED")   # conftest default
    chip_smoke.run(TOY, CPU)
    out = capsys.readouterr().out
    assert "multichip phase NOT run" in out
    assert "bin15 serve requests" in out


@pytest.mark.slow
def test_multichip_phase_at_toy_size(monkeypatch):
    """The four-chip phase on the 8-device virtual mesh (slow: a second
    train compile; run it before spending 4x chip-minutes)."""
    monkeypatch.delenv("LIGHTGBM_TPU_DISABLE_BATCHED")
    chip_smoke.run(TOY._replace(multichip=True), CPU)


def test_failed_check_raises(monkeypatch):
    """A phase that does not meet its expectation stops the run."""
    monkeypatch.delenv("LIGHTGBM_TPU_DISABLE_BATCHED")
    with pytest.raises(AssertionError, match="binned on 'host'"):
        chip_smoke.run(TOY, CPU._replace(binned_on="device"))


def test_script_refuses_cpu_backend():
    """Un-overridden, on a CPU backend: non-zero exit, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("LIGHTGBM_TPU_DISABLE_BATCHED", None)
    proc = subprocess.run([sys.executable, os.path.join(ROOT,
                                                        "chip_smoke.py")],
                          env=env, cwd=ROOT, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "a TPU is required" in proc.stderr
