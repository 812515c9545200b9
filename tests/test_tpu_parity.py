"""TPU-vs-portable parity gates (run on real TPU hardware; SKIPPED on the
CPU test mesh — the analog of the reference's GPU/CPU dual test,
tests/python_package_test/test_dual.py:19).

These exercise the device-only code paths that CPU CI cannot reach: the
wave megakernel, the wide/categorical/EFB wave-apply path (grow_wave.py
dec_go_left + wave_apply_pallas), and the device batch predictor. Ground
truth is the SAME training run on the portable XLA path, in the SAME
process: a chip belongs to one process, and the
LIGHTGBM_TPU_DISABLE_PALLAS kill switch is read at trace time, which
every new Booster's jitted closures go through again.

On the chip:  LIGHTGBM_TPU_TEST_ON_TPU=1 python -m pytest tests/test_tpu_parity.py
"""

import os

import numpy as np
import pytest

import lightgbm_tpu as lgb

# the env switch, not a backend probe: collecting this file on the CPU
# mesh must not initialize a backend (tests/conftest.py reads the same)
pytestmark = pytest.mark.skipif(
    os.environ.get("LIGHTGBM_TPU_TEST_ON_TPU", "") != "1",
    reason="needs a real TPU backend (LIGHTGBM_TPU_TEST_ON_TPU=1)")


@pytest.fixture(scope="module", autouse=True)
def _tpu():
    from lightgbm_tpu.runtime.device import require_tpu
    return require_tpu()


def _pallas_vs_portable(monkeypatch, params, X, y, rounds=10, **dskw):
    """Train twice on the SAME backend, in this process: once on the
    portable XLA lowering (kill switch set while the first Booster
    traces), once with the Pallas kernels; return both predictions."""
    def fit():
        b = lgb.train(params, lgb.Dataset(X, label=y, **dskw),
                      num_boost_round=rounds)
        return b.predict(X[:20000])

    monkeypatch.setenv("LIGHTGBM_TPU_DISABLE_PALLAS", "1")
    ref = fit()
    monkeypatch.delenv("LIGHTGBM_TPU_DISABLE_PALLAS")
    return fit(), ref


def test_wide_feature_parity(monkeypatch):
    """F=64 > 32 exercises wave_apply_pallas + the F-gridded slots
    kernel against the portable select-chain path."""
    rng = np.random.RandomState(0)
    N, F = 120_000, 64
    X = rng.normal(size=(N, F)).astype(np.float32)
    w = rng.normal(size=F) * (rng.uniform(size=F) < 0.4)
    y = (X @ w + rng.normal(scale=0.5, size=N) > 0).astype(np.float32)
    params = dict(objective="binary", num_leaves=63, max_bin=63,
                  verbose=-1)
    got, ref = _pallas_vs_portable(monkeypatch, params, X, y)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


def test_categorical_parity(monkeypatch):
    rng = np.random.RandomState(1)
    N = 100_000
    Xc = rng.randint(0, 24, size=(N, 2)).astype(np.float32)
    Xn = rng.normal(size=(N, 6)).astype(np.float32)
    X = np.concatenate([Xc, Xn], axis=1)
    y = (((Xc[:, 0] % 5 == 0) | (Xc[:, 1] % 7 == 1))
         ^ (Xn[:, 0] > 0)).astype(np.float32)
    params = dict(objective="binary", num_leaves=31, max_bin=63,
                  verbose=-1, min_data_in_leaf=20)
    got, ref = _pallas_vs_portable(monkeypatch, params, X, y,
                                   categorical_feature=[0, 1])
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


def test_efb_parity(monkeypatch):
    """Sparse one-hot-ish features trigger EFB bundling; the bundled
    storage drives dec_go_left's unpack path on TPU."""
    rng = np.random.RandomState(2)
    N, F = 100_000, 60
    X = np.zeros((N, F), np.float32)
    hot = rng.randint(0, F // 2, size=N)
    X[np.arange(N), hot] = rng.uniform(1, 3, size=N).astype(np.float32)
    X[:, F // 2:] = rng.normal(size=(N, F - F // 2))
    y = ((hot % 3 == 0) ^ (X[:, F // 2] > 0)).astype(np.float32)
    params = dict(objective="binary", num_leaves=31, max_bin=63,
                  verbose=-1, enable_bundle=True)
    got, ref = _pallas_vs_portable(monkeypatch, params, X, y)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


def test_device_predict_routes_and_matches_host():
    rng = np.random.RandomState(3)
    N, F = 150_000, 16
    X = rng.normal(size=(N, F)).astype(np.float32)
    X[::13, 3] = np.nan
    y = (np.nansum(X[:, :4], axis=1) > 0).astype(np.float32)
    b = lgb.train(dict(objective="binary", num_leaves=63, verbose=-1),
                  lgb.Dataset(X, label=y), num_boost_round=10)
    pd = b.predict(X)                      # routes to the device path
    pm = b._gbdt._packed_model(0, len(b._gbdt.models))
    ph = 1.0 / (1.0 + np.exp(-pm.predict_margin(X)[0]))
    np.testing.assert_allclose(pd, ph, rtol=2e-5, atol=2e-6)
