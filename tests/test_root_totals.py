"""A node's totals have one owner: every grower reads the root's gradient
and hessian sums off the root histogram (``ops/split.py:root_totals``),
so they are sums of the same values as the bins that every child's
"parent minus left" is taken from, whatever the histogram did to its
operands on the way in.

The case gives each grower a root histogram that rounds its operands to
bfloat16, as the chip's kernels do (``ops/histogram_pallas.py:_make_W``),
on the portable path, and reads the root's totals back from the second
tree's first node (``internal_weight`` is the root's ``leaf_sum_h``,
``internal_value`` its output, -lr * sum_g / sum_h). The second tree,
because the first tree's gradients (+-0.5, 0.25) are exact in bfloat16.
With float32 row sums beside such a histogram, as the growers had them
until PR 34, the hessian total is off by 6.2e-4 of itself and the root's
output by 6.4 % (my CPU runs, PR 34: the parent commit on this case, the
same on all three growers); read off the histogram they read 0.0 and
1.8e-8.
"""

import importlib

import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb

GROWERS = {"masked": "grow", "compact": "grow_fast", "wave": "grow_wave"}
LR = 0.1


@pytest.mark.parametrize("grower", list(GROWERS))
def test_root_totals_are_the_root_histograms_sums(grower, monkeypatch):
    mod = importlib.import_module(f"lightgbm_tpu.ops.{GROWERS[grower]}")
    real = mod.build_histogram

    def rounds_its_operands(X_t, vals, *args, **kwargs):
        return real(X_t, vals.astype(jnp.bfloat16).astype(jnp.float32),
                    *args, **kwargs)

    monkeypatch.setattr(mod, "build_histogram", rounds_its_operands)
    rng = np.random.RandomState(11)
    X = rng.normal(size=(1000, 6)).astype(np.float32)
    y = (X @ rng.normal(size=6) + rng.normal(size=1000) > 0).astype(
        np.float32)
    bst = lgb.train(
        {"objective": "binary", "boost_from_average": False,
         "num_leaves": 4, "min_data_in_leaf": 5, "learning_rate": LR,
         "verbose": -1, "tpu_grower": grower},
        lgb.Dataset(X, label=y), num_boost_round=2)
    p = 1.0 / (1.0 + np.exp(-bst.predict(X, raw_score=True,
                                         num_iteration=1)))
    sum_g, sum_h = (
        float(np.sum(v.astype(np.float32).astype(jnp.bfloat16)
                     .astype(np.float64))) for v in (p - y, p * (1.0 - p)))
    root = bst._gbdt.models[1]
    assert root.num_leaves > 1
    np.testing.assert_allclose(root.internal_weight[0], sum_h, rtol=2e-6)
    np.testing.assert_allclose(root.internal_value[0],
                               -LR * sum_g / sum_h, rtol=2e-5)
