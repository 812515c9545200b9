"""Random Forest mode.

reference: src/boosting/rf.hpp:26 — bagging without shrinkage; gradients are
computed ONCE from the constant boost-from-average score (RF::Boosting,
rf.hpp:96-117), every tree trains against them on its bag, and the model
output is the AVERAGE over iterations (average_output_, rf.hpp:29).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..utils.log import log_fatal
from .gbdt import GBDT


class RF(GBDT):
    """reference: class RF (src/boosting/rf.hpp:26)."""

    def __init__(self, config, train_set, objective, training_metrics=()):
        super().__init__(config, train_set, objective, training_metrics)
        self.average_output = True
        self.shrinkage_rate = 1.0
        if train_set is not None:
            self._init_fixed_gradients()

    def _init_fixed_gradients(self) -> None:
        """RF::Boosting (rf.hpp:96): gradients from the constant
        boost-from-average score."""
        if self.objective is None:
            log_fatal("RF mode does not support custom objective functions, "
                      "please use built-in objectives")
        K = self.num_tree_per_iteration
        N = self.N_pad
        init_scores = np.zeros(K)
        if self.config.boost_from_average and not self._has_init_score:
            for k in range(K):
                init_scores[k] = self.objective.boost_from_score(k)
        self._init_scores = init_scores
        tmp = np.tile(np.asarray(init_scores, np.float32)[:, None], (1, N))
        if self.objective.runs_on_host:
            g, h = self.objective.get_gradients_numpy(
                tmp[:, :self.num_data].reshape(-1))
            g = g.reshape(K, -1)
            h = h.reshape(K, -1)
            if N != self.num_data:
                pad = ((0, 0), (0, N - self.num_data))
                g, h = np.pad(g, pad), np.pad(h, pad)
            self._fixed_g = self._put_rows(jnp.asarray(g), row_axis=1)
            self._fixed_h = self._put_rows(jnp.asarray(h), row_axis=1)
        else:
            scores_dev = self._put_rows(jnp.asarray(tmp), row_axis=1)
            self._fixed_g, self._fixed_h = self._grad_fn(
                scores_dev, self.label_dev, self.weight_dev,
                self.objective.device_state())

    # -- overrides ----------------------------------------------------
    def _boost_from_average(self) -> np.ndarray:
        # RF never folds a bias into trees or scores
        return np.zeros(self.num_tree_per_iteration)

    def boost(self):
        return self._fixed_g, self._fixed_h

    def train_one_iter(self, grad=None, hess=None) -> bool:
        """After the base iteration, fold the boost-from-average bias into
        each new tree (rf.hpp:150-156 AddBias) so averaged predictions and
        maintained scores carry the init score."""
        ret = super().train_one_iter(grad, hess)
        K = self.num_tree_per_iteration
        for k in range(K):
            b = float(self._init_scores[k])
            if abs(b) > 1e-15 and len(self.models) >= K:
                tree = self.models[-K + k]
                tree.add_bias(b)
                self.scores = self.scores.at[k].add(jnp.float32(b))
                for vi in range(len(self._valid_scores)):
                    self._valid_scores[vi] = \
                        self._valid_scores[vi].at[k].add(jnp.float32(b))
        return ret

    def get_eval_result(self, metrics_per_set):
        """Metrics see the AVERAGED score (rf.hpp MultiplyScore handling)."""
        it = max(self.iter, 1)
        saved, saved_v = self.scores, list(self._valid_scores)
        self.scores = self.scores / it
        self._valid_scores = [v / it for v in saved_v]
        try:
            return super().get_eval_result(metrics_per_set)
        finally:
            self.scores, self._valid_scores = saved, saved_v
