"""Evaluation metrics.

Host-side numpy analogs of src/metric/* (factory: src/metric/metric.cpp:88).
Each metric returns (name, value, is_higher_better). Scores arrive as raw
model output; metrics apply the objective's output transform themselves the
way the reference metrics take the ObjectiveFunction's ConvertOutput.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..utils.log import log_fatal, log_warning

_KEPS = 1e-15
# device metrics run in f32: 1e-15 would round to 0 there and log(0)
# follows — clip at the smallest eps that survives `1 - eps` in f32
_KEPS_F32 = 1e-7

MetricResult = Tuple[str, float, bool]  # (name, value, is_higher_better)


def _device_convert_output(objective):
    """jnp analog of `objective.convert_output` for in-scan metric eval
    (docs/PERF.md §7). Returns identity when no transform is needed and
    None when the objective's transform has no device analog — the
    trainer then falls back to per-iteration host evaluation."""
    if objective is None or not objective.need_convert_output:
        return lambda s: s
    name = getattr(objective, "name", "")
    cfg = objective.config
    if name == "binary" or name == "multiclassova":
        sig = float(cfg.sigmoid)
        return lambda s: 1.0 / (1.0 + jnp.exp(-sig * s))
    if name == "multiclass":
        return lambda s: jax.nn.softmax(s, axis=0)
    if name in ("poisson", "gamma", "tweedie"):
        return lambda s: jnp.exp(s)
    return None


class Metric:
    name: str = ""
    is_higher_better: bool = False

    def __init__(self, config: Config):
        self.config = config

    def init(self, metadata, num_data: int) -> None:
        self.label = metadata.label
        self.weight = metadata.weight
        self.query_boundaries = metadata.query_boundaries
        self.num_data = num_data
        if self.weight is None:
            self.sum_weights = float(num_data)
        else:
            self.sum_weights = float(np.sum(self.weight))

    def eval(self, score: np.ndarray, objective) -> List[MetricResult]:
        raise NotImplementedError

    def result_name(self) -> str:
        """Name under which eval() reports its (single) result — only
        multi_error@k differs from the class-level name."""
        return self.name

    def result_names(self) -> List[str]:
        """Names of everything eval() reports, in its order: one column
        each of the in-scan metric stack."""
        return [self.result_name()]

    def device_eval_fn(self, objective) -> Optional[Callable]:
        """Traceable `fn(score, label, weight, sum_weights) -> f32 scalar`
        evaluating this metric on device inside a scan body, or None when
        no device analog exists (batched training then routes through the
        per-iteration host loop). Device values are f32 — low-bit
        divergence from the f64 host value is expected and documented.

        A metric that reports several results returns an f32 vector in
        ``result_names()`` order; one that owns device state built from
        the data (``device_state()`` not None) takes it as a fifth
        argument, `fn(score, label, weight, sum_weights, state)`."""
        return None

    def device_state(self):
        """Pytree of device arrays the device fn reads, or None; the
        trainer passes it through its jitted programs' arguments."""
        return None

    def _w(self) -> np.ndarray:
        if self.weight is not None:
            return self.weight.astype(np.float64)
        return np.ones(self.num_data, dtype=np.float64)


class _PointwiseRegressionMetric(Metric):
    """reference: regression_metric.hpp RegressionMetric<T>."""

    transform_output = True
    _device_point_loss = None  # staticmethod (cfg, y, s) -> loss, or None

    def point_loss(self, label: np.ndarray, score: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def final_transform(self, mean_loss: float) -> float:
        return mean_loss

    def _device_final(self, v):
        return v

    def device_eval_fn(self, objective):
        if self._device_point_loss is None:
            return None
        conv = _device_convert_output(objective) if self.transform_output \
            else (lambda s: s)
        if conv is None:
            return None
        point, final, cfg = self._device_point_loss, self._device_final, \
            self.config

        def fn(score, label, weight, sum_weights):
            s = conv(jnp.reshape(score, (-1,)))
            return final(jnp.sum(point(cfg, label, s) * weight)
                         / sum_weights)
        return fn

    def eval(self, score, objective) -> List[MetricResult]:
        score = np.asarray(score, np.float64).reshape(-1)
        if objective is not None and self.transform_output \
                and objective.need_convert_output:
            score = objective.convert_output(score)
        label = self.label.astype(np.float64)
        w = self._w()
        loss = float(np.sum(self.point_loss(label, score) * w) / self.sum_weights)
        return [(self.name, self.final_transform(loss), self.is_higher_better)]


class L2Metric(_PointwiseRegressionMetric):
    name = "l2"
    _device_point_loss = staticmethod(lambda cfg, y, s: (s - y) ** 2)

    def point_loss(self, y, s):
        return (s - y) ** 2


class RMSEMetric(L2Metric):
    name = "rmse"

    def final_transform(self, v):
        return float(np.sqrt(v))

    def _device_final(self, v):
        return jnp.sqrt(v)


class L1Metric(_PointwiseRegressionMetric):
    name = "l1"
    _device_point_loss = staticmethod(lambda cfg, y, s: jnp.abs(s - y))

    def point_loss(self, y, s):
        return np.abs(s - y)


class QuantileMetric(_PointwiseRegressionMetric):
    name = "quantile"
    _device_point_loss = staticmethod(
        lambda cfg, y, s: jnp.where(
            (y - s) >= 0, cfg.alpha * (y - s), (cfg.alpha - 1.0) * (y - s)))

    def point_loss(self, y, s):
        a = self.config.alpha
        d = y - s
        return np.where(d >= 0, a * d, (a - 1.0) * d)


class HuberMetric(_PointwiseRegressionMetric):
    name = "huber"

    def point_loss(self, y, s):
        a = self.config.alpha
        d = np.abs(s - y)
        return np.where(d <= a, 0.5 * d * d, a * (d - 0.5 * a))


class FairMetric(_PointwiseRegressionMetric):
    name = "fair"

    def point_loss(self, y, s):
        c = self.config.fair_c
        x = np.abs(s - y)
        return c * x - c * c * np.log1p(x / c)


class PoissonMetric(_PointwiseRegressionMetric):
    name = "poisson"

    def point_loss(self, y, s):
        eps = 1e-10
        s = np.maximum(s, eps)
        return s - y * np.log(s)


class MAPEMetric(_PointwiseRegressionMetric):
    name = "mape"

    def point_loss(self, y, s):
        return np.abs((y - s)) / np.maximum(1.0, np.abs(y))


class GammaMetric(_PointwiseRegressionMetric):
    """Gamma negative log-likelihood with psi=1
    (reference: regression_metric.hpp GammaMetric): y/s + log(s)."""
    name = "gamma"

    def point_loss(self, y, s):
        s = np.maximum(s, 1e-10)
        return y / s + np.log(s)


class GammaDevianceMetric(_PointwiseRegressionMetric):
    """reference: regression_metric.hpp GammaDevianceMetric:
    2*(frac - log(frac) - 1), frac = label/score."""
    name = "gamma_deviance"

    def point_loss(self, y, s):
        eps = 1e-9
        frac = np.maximum(y / np.maximum(s, eps), eps)
        return 2.0 * (frac - np.log(frac) - 1.0)


class TweedieMetric(_PointwiseRegressionMetric):
    name = "tweedie"

    def point_loss(self, y, s):
        rho = self.config.tweedie_variance_power
        eps = 1e-10
        s = np.maximum(s, eps)
        a = y * np.power(s, 1.0 - rho) / (1.0 - rho)
        b = np.power(s, 2.0 - rho) / (2.0 - rho)
        return -a + b


class R2Metric(_PointwiseRegressionMetric):
    name = "r2"
    is_higher_better = True

    def eval(self, score, objective):
        score = np.asarray(score, np.float64).reshape(-1)
        if objective is not None and objective.need_convert_output:
            score = objective.convert_output(score)
        y = self.label.astype(np.float64)
        w = self._w()
        ybar = np.sum(y * w) / self.sum_weights
        ss_res = np.sum(w * (y - score) ** 2)
        ss_tot = np.sum(w * (y - ybar) ** 2)
        return [(self.name, float(1.0 - ss_res / max(ss_tot, _KEPS)), True)]


# ---------------------------------------------------------------------------
# binary metrics (reference: binary_metric.hpp:116-271)
# ---------------------------------------------------------------------------
class BinaryLoglossMetric(Metric):
    name = "binary_logloss"

    def eval(self, score, objective) -> List[MetricResult]:
        p = objective.convert_output(np.asarray(score, np.float64).reshape(-1)) \
            if objective is not None and objective.need_convert_output else \
            1.0 / (1.0 + np.exp(-np.asarray(score, np.float64).reshape(-1)))
        y = (self.label > 0).astype(np.float64)
        p = np.clip(p, _KEPS, 1.0 - _KEPS)
        loss = -(y * np.log(p) + (1 - y) * np.log(1 - p))
        w = self._w()
        return [(self.name, float(np.sum(loss * w) / self.sum_weights), False)]

    def device_eval_fn(self, objective):
        if objective is not None and objective.need_convert_output:
            conv = _device_convert_output(objective)
            if conv is None:
                return None
        else:
            conv = lambda s: 1.0 / (1.0 + jnp.exp(-s))  # noqa: E731

        def fn(score, label, weight, sum_weights):
            p = conv(jnp.reshape(score, (-1,)))
            y = (label > 0).astype(jnp.float32)
            p = jnp.clip(p, _KEPS_F32, 1.0 - _KEPS_F32)
            loss = -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))
            return jnp.sum(loss * weight) / sum_weights
        return fn


class BinaryErrorMetric(Metric):
    name = "binary_error"

    def eval(self, score, objective) -> List[MetricResult]:
        p = objective.convert_output(np.asarray(score, np.float64).reshape(-1)) \
            if objective is not None and objective.need_convert_output else \
            np.asarray(score, np.float64).reshape(-1)
        y = (self.label > 0)
        pred = p > 0.5
        w = self._w()
        err = (pred != y).astype(np.float64)
        return [(self.name, float(np.sum(err * w) / self.sum_weights), False)]

    def device_eval_fn(self, objective):
        if objective is not None and objective.need_convert_output:
            conv = _device_convert_output(objective)
            if conv is None:
                return None
        else:
            conv = lambda s: s  # noqa: E731

        def fn(score, label, weight, sum_weights):
            p = conv(jnp.reshape(score, (-1,)))
            err = ((p > 0.5) != (label > 0)).astype(jnp.float32)
            return jnp.sum(err * weight) / sum_weights
        return fn


class AUCMetric(Metric):
    """reference: binary_metric.hpp AUCMetric (weighted rank sum)."""
    name = "auc"
    is_higher_better = True

    def eval(self, score, objective) -> List[MetricResult]:
        s = np.asarray(score, np.float64).reshape(-1)
        y = (self.label > 0)
        w = self._w()
        order = np.argsort(s, kind="mergesort")
        s_s, y_s, w_s = s[order], y[order], w[order]
        # tie-aware trapezoid accumulation
        pos_w = np.sum(w_s * y_s)
        neg_w = np.sum(w_s * ~y_s)
        if pos_w <= 0 or neg_w <= 0:
            return [(self.name, 1.0, True)]
        # group by unique score
        _, idx_start = np.unique(s_s, return_index=True)
        group_pos = np.add.reduceat(w_s * y_s, idx_start)
        group_neg = np.add.reduceat(w_s * ~y_s, idx_start)
        cum_neg = np.cumsum(group_neg) - group_neg
        auc = np.sum(group_pos * (cum_neg + 0.5 * group_neg)) / (pos_w * neg_w)
        return [(self.name, float(auc), True)]

    def device_eval_fn(self, objective):
        # AUC is rank-based: no output transform needed (monotone convert
        # preserves the ordering, as on the host path)
        def fn(score, label, weight, sum_weights):
            s = jnp.reshape(score, (-1,))
            n = s.shape[0]
            order = jnp.argsort(s)  # stable ascending, mirrors mergesort
            s_s, y_s, w_s = s[order], (label > 0)[order], weight[order]
            yw = w_s * y_s.astype(jnp.float32)
            nw = w_s * (~y_s).astype(jnp.float32)
            pos_w, neg_w = jnp.sum(yw), jnp.sum(nw)
            # tie groups: consecutive equal scores share a group id
            gid = jnp.concatenate([
                jnp.zeros((1,), jnp.int32),
                jnp.cumsum((s_s[1:] != s_s[:-1]).astype(jnp.int32))])
            group_pos = jax.ops.segment_sum(yw, gid, num_segments=n)
            group_neg = jax.ops.segment_sum(nw, gid, num_segments=n)
            cum_neg = jnp.cumsum(group_neg) - group_neg
            auc = jnp.sum(group_pos * (cum_neg + 0.5 * group_neg)) \
                / jnp.maximum(pos_w * neg_w, _KEPS_F32)
            # degenerate single-class valid set reports 1.0 like the host
            return jnp.where((pos_w <= 0) | (neg_w <= 0),
                             jnp.float32(1.0), auc)
        return fn


class AveragePrecisionMetric(Metric):
    name = "average_precision"
    is_higher_better = True

    def eval(self, score, objective) -> List[MetricResult]:
        s = np.asarray(score, np.float64).reshape(-1)
        y = (self.label > 0).astype(np.float64)
        w = self._w()
        order = np.argsort(-s, kind="mergesort")
        y_s, w_s = y[order], w[order]
        tp = np.cumsum(w_s * y_s)
        fp = np.cumsum(w_s * (1 - y_s))
        total_pos = tp[-1]
        if total_pos <= 0:
            return [(self.name, 1.0, True)]
        precision = tp / np.maximum(tp + fp, _KEPS)
        recall = tp / total_pos
        d_recall = np.diff(np.concatenate([[0.0], recall]))
        ap = float(np.sum(precision * d_recall))
        return [(self.name, ap, True)]


# ---------------------------------------------------------------------------
# multiclass metrics (reference: multiclass_metric.hpp)
# ---------------------------------------------------------------------------
class MultiLoglossMetric(Metric):
    name = "multi_logloss"

    def eval(self, score, objective) -> List[MetricResult]:
        # score: [K, N] raw
        s = np.asarray(score, np.float64)
        p = objective.convert_output(s) if objective is not None \
            and objective.need_convert_output else s
        li = self.label.astype(np.int64)
        pi = np.clip(p[li, np.arange(len(li))], _KEPS, 1.0)
        w = self._w()
        loss = float(np.sum(-np.log(pi) * w) / self.sum_weights)
        return [(self.name, loss, False)]

    def device_eval_fn(self, objective):
        conv = _device_convert_output(objective)
        if conv is None:
            return None

        def fn(score, label, weight, sum_weights):
            p = conv(score)  # [K, N]
            li = label.astype(jnp.int32)
            pi = p[li, jnp.arange(p.shape[1])]
            pi = jnp.clip(pi, _KEPS_F32, 1.0)
            return jnp.sum(-jnp.log(pi) * weight) / sum_weights
        return fn


class MultiErrorMetric(Metric):
    name = "multi_error"

    def eval(self, score, objective) -> List[MetricResult]:
        s = np.asarray(score, np.float64)
        li = self.label.astype(np.int64)
        k = self.config.multi_error_top_k
        w = self._w()
        if k <= 1:
            pred = np.argmax(s, axis=0)
            err = (pred != li).astype(np.float64)
        else:
            # top-k error: 1 if the true class is not among the k largest
            part = np.argpartition(-s, k - 1, axis=0)[:k]
            hit = np.any(part == li[None, :], axis=0)
            err = (~hit).astype(np.float64)
        name = self.name if k <= 1 else f"multi_error@{k}"
        return [(name, float(np.sum(err * w) / self.sum_weights), False)]

    def result_name(self) -> str:
        k = self.config.multi_error_top_k
        return self.name if k <= 1 else f"multi_error@{k}"

    def device_eval_fn(self, objective):
        # argmax/top-k membership is transform-invariant, raw scores ok
        k = self.config.multi_error_top_k

        def fn(score, label, weight, sum_weights):
            li = label.astype(jnp.int32)
            if k <= 1:
                err = (jnp.argmax(score, axis=0) != li)
            else:
                _, topi = jax.lax.top_k(score.T, k)  # [N, k]
                err = ~jnp.any(topi == li[:, None], axis=1)
            return jnp.sum(err.astype(jnp.float32) * weight) / sum_weights
        return fn


# ---------------------------------------------------------------------------
# ranking metrics (reference: rank_metric.hpp:20, map_metric.hpp:21)
# ---------------------------------------------------------------------------
class NDCGMetric(Metric):
    name = "ndcg"
    is_higher_better = True
    # live cells of one block of queries while it is ranked (plen x plen
    # comparisons a query), as LambdarankNDCG.pair_block_bytes
    rank_block_bytes = 256 << 20

    def init(self, metadata, num_data: int) -> None:
        super().init(metadata, num_data)
        self._state = None

    def device_state(self):
        """Built on first use (``GBDT.add_valid_dataset`` asks once, so a
        valid set's build falls under its ``booster/add_valid`` span; a
        training metric is never asked and builds nothing)."""
        if self._state is None and self.query_boundaries is not None \
                and self.label is not None:
            from ..runtime.profiler import count as span_count, span
            with span("metric/init", rows=self.num_data,
                      queries=len(self.query_boundaries) - 1):
                self._state, cells, n_buckets = self._build_device_state()
                span_count(padded_rows=cells, buckets=n_buckets)
        return self._state

    def _build_device_state(self):
        """The query buckets of ``rank_buckets`` (the layout the device
        LambdaRank objective reads through) with, per query, the label
        gains, the inverse max DCG at each ``eval_at`` (0 where a query
        has no relevant document: it then counts 1, rank_metric.hpp) and
        the query's weight (its first row's, as eval_ndcg)."""
        from .rank_buckets import (RANK_TEMPS, bucket_labels, build_buckets,
                                   inverse_max_dcg_at, label_gains,
                                   queries_per_block)
        from .rank_utils import default_label_gain
        ks = [int(k) for k in self.config.eval_at]
        lg = np.asarray(self.config.label_gain, np.float64) \
            if len(self.config.label_gain) else default_label_gain(
                int(np.max(self.label)) if len(self.label) else 1)
        buckets, _ = build_buckets(
            self.query_boundaries, self.num_data,
            lambda plen: queries_per_block(plen * plen * RANK_TEMPS,
                                           self.rank_block_bytes // 4))
        qb = np.asarray(self.query_boundaries, np.int64)
        dev, cells = [], 0
        for bk in buckets:
            lab = bucket_labels(bk, self.label)
            imd = inverse_max_dcg_at(lab, lg, ks)
            live = bk["qids"] >= 0         # a block's tail: empty queries
            qw = live.astype(np.float64) if self.weight is None \
                else np.where(live, np.asarray(self.weight, np.float64)[
                    qb[np.maximum(bk["qids"], 0)]], 0.0)
            dev.append({"idx": jnp.asarray(bk["idx"]),
                        "gain": jnp.asarray(label_gains(lab, lg)),
                        "cnt": jnp.asarray(bk["cnt"]),
                        "imd": jnp.asarray(imd.astype(np.float32)),
                        "qw": jnp.asarray(qw.astype(np.float32))})
            cells += lab.size
        sumw = float(len(qb) - 1) if self.weight is None else float(
            np.sum(np.asarray(self.weight, np.float64)[qb[:-1]]))
        state = {"buckets": tuple(dev), "sumw": jnp.float32(sumw)}
        return state, cells, len(buckets)

    def result_names(self) -> List[str]:
        return [f"ndcg@{k}" for k in self.config.eval_at]

    def device_eval_fn(self, objective):
        if self.device_state() is None:
            return None           # no groups: eval() reports it
        from .rank_buckets import gather_scores, map_blocks, rank_by_score
        ks = jnp.asarray([int(k) for k in self.config.eval_at], jnp.int32)

        def block(s, bk):
            """[len(eval_at)]: the weighted NDCG sums of one block of
            queries: each cell's rank by counting (stable: ties keep
            row order), then the gains of the ranks under k,
            discounted, summed in float32."""
            rank, has_row = rank_by_score(
                gather_scores(s, bk["idx"]), bk["cnt"])
            d = jnp.where(has_row, bk["gain"]
                          / jnp.log2(2.0 + rank.astype(jnp.float32)), 0.0)
            dcg = jnp.sum(jnp.where(
                rank[:, None, :] < ks[None, :, None], d[:, None, :], 0.0),
                axis=2)                                      # [nq, k]
            ndcg = jnp.where(bk["imd"] > 0, dcg * bk["imd"], 1.0)
            return jnp.sum(ndcg * bk["qw"][:, None], axis=0)

        def fn(score, label, weight, sum_weights, state):
            """NDCG at every ``eval_at`` of the flat raw scores."""
            with jax.named_scope("lgbm_rank_ndcg"):
                s = jnp.reshape(score, (-1,))
                total = jnp.zeros(ks.shape, jnp.float32)
                for bk in state["buckets"]:
                    part = map_blocks(lambda b: block(s, b), bk)
                    total = total + part.reshape(-1, part.shape[-1]).sum(0)
                return total / state["sumw"]
        return fn

    def eval(self, score, objective) -> List[MetricResult]:
        from .rank_utils import eval_ndcg
        s = np.asarray(score, np.float64).reshape(-1)
        return eval_ndcg(s, self.label, self.query_boundaries,
                         self.weight, self.config.eval_at,
                         self.config.label_gain)


class MapMetric(Metric):
    name = "map"
    is_higher_better = True

    def eval(self, score, objective) -> List[MetricResult]:
        from .rank_utils import eval_map
        s = np.asarray(score, np.float64).reshape(-1)
        return eval_map(s, self.label, self.query_boundaries,
                        self.weight, self.config.eval_at)


# ---------------------------------------------------------------------------
# cross-entropy metrics (reference: xentropy_metric.hpp)
# ---------------------------------------------------------------------------
class CrossEntropyMetric(Metric):
    name = "cross_entropy"

    def eval(self, score, objective) -> List[MetricResult]:
        p = np.asarray(score, np.float64).reshape(-1)
        if objective is not None and objective.need_convert_output:
            p = objective.convert_output(p)
        else:
            p = 1.0 / (1.0 + np.exp(-p))
        y = self.label.astype(np.float64)
        p = np.clip(p, _KEPS, 1.0 - _KEPS)
        loss = -(y * np.log(p) + (1 - y) * np.log(1 - p))
        w = self._w()
        return [(self.name, float(np.sum(loss * w) / self.sum_weights), False)]


class KLDivMetric(Metric):
    name = "kullback_leibler"

    def eval(self, score, objective) -> List[MetricResult]:
        p = np.asarray(score, np.float64).reshape(-1)
        if objective is not None and objective.need_convert_output:
            p = objective.convert_output(p)
        else:
            p = 1.0 / (1.0 + np.exp(-p))
        y = np.clip(self.label.astype(np.float64), _KEPS, 1 - _KEPS)
        p = np.clip(p, _KEPS, 1.0 - _KEPS)
        kl = y * np.log(y / p) + (1 - y) * np.log((1 - y) / (1 - p))
        w = self._w()
        return [(self.name, float(np.sum(kl * w) / self.sum_weights), False)]


class AucMuMetric(Metric):
    """Multi-class AUC-mu (reference: multiclass_metric.hpp:184, after
    Kleiman & Page, pmlr v97). Pairwise class separability measured along
    the partition-weight direction, averaged over class pairs."""
    name = "auc_mu"
    is_higher_better = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        nc = self.config.num_class
        wspec = self.config.auc_mu_weights
        if wspec:
            if len(wspec) != nc * nc:
                from ..utils.log import log_fatal
                log_fatal(f"auc_mu_weights must have {nc * nc} elements")
            self._cw = np.asarray(wspec, np.float64).reshape(nc, nc)
            np.fill_diagonal(self._cw, 0.0)
        else:
            self._cw = np.ones((nc, nc)) - np.eye(nc)

    def eval(self, score, objective) -> List[MetricResult]:
        nc = self.config.num_class
        s = np.asarray(score, np.float64).reshape(nc, -1)
        lab = self.label.astype(np.int64)
        w = self.weight
        ans = 0.0
        eps = 1e-15
        for i in range(nc):
            for j in range(i + 1, nc):
                curr_v = self._cw[i] - self._cw[j]
                t1 = curr_v[i] - curr_v[j]
                sel = (lab == i) | (lab == j)
                idx = np.flatnonzero(sel)
                va = t1 * (curr_v @ s[:, idx])
                # sort by distance; ties put class j first (higher label).
                # Within a tie group all j rows therefore precede all i
                # rows, so the reference's sequential 0.5-credit rule is
                # equivalent to: each i row counts the j weight of all
                # groups up to its own, minus half its own group's.
                order = np.lexsort((-lab[idx], va))
                a = idx[order]
                dist = va[order]
                is_i = lab[a] == i
                wt = np.ones(len(a)) if w is None else \
                    np.asarray(w, np.float64)[a]
                grp = np.zeros(len(a), np.int64)
                if len(a) > 1:
                    grp[1:] = np.cumsum(np.abs(np.diff(dist)) >= eps)
                jw = np.where(is_i, 0.0, wt)
                j_in = np.bincount(grp, weights=jw)
                j_incl = np.cumsum(j_in)
                sij = float(np.sum(
                    wt[is_i] * (j_incl[grp[is_i]]
                                - 0.5 * j_in[grp[is_i]])))
                if w is None:
                    ci = float(np.sum(lab == i))
                    cj = float(np.sum(lab == j))
                else:
                    ww = np.asarray(w, np.float64)
                    ci = float(np.sum(ww[lab == i]))
                    cj = float(np.sum(ww[lab == j]))
                if ci > 0 and cj > 0:
                    ans += (sij / ci) / cj
        ans = (2.0 * ans / nc) / (nc - 1)
        return [(self.name, float(ans), True)]


_METRIC_REGISTRY = {
    "l2": L2Metric, "mean_squared_error": L2Metric, "mse": L2Metric,
    "regression": L2Metric, "regression_l2": L2Metric,
    "rmse": RMSEMetric, "root_mean_squared_error": RMSEMetric,
    "l2_root": RMSEMetric,
    "l1": L1Metric, "mean_absolute_error": L1Metric, "mae": L1Metric,
    "regression_l1": L1Metric,
    "quantile": QuantileMetric,
    "huber": HuberMetric,
    "fair": FairMetric,
    "poisson": PoissonMetric,
    "mape": MAPEMetric, "mean_absolute_percentage_error": MAPEMetric,
    "gamma": GammaMetric,
    "gamma_deviance": GammaDevianceMetric,
    "tweedie": TweedieMetric,
    "r2": R2Metric,
    "binary_logloss": BinaryLoglossMetric, "binary": BinaryLoglossMetric,
    "binary_error": BinaryErrorMetric,
    "auc": AUCMetric,
    "auc_mu": AucMuMetric,
    "average_precision": AveragePrecisionMetric,
    "multi_logloss": MultiLoglossMetric, "multiclass": MultiLoglossMetric,
    "softmax": MultiLoglossMetric, "multiclassova": MultiLoglossMetric,
    "multi_error": MultiErrorMetric,
    "ndcg": NDCGMetric, "lambdarank": NDCGMetric,
    "rank_xendcg": NDCGMetric, "xendcg": NDCGMetric,
    "map": MapMetric, "mean_average_precision": MapMetric,
    "cross_entropy": CrossEntropyMetric, "xentropy": CrossEntropyMetric,
    "cross_entropy_lambda": CrossEntropyMetric,
    "xentlambda": CrossEntropyMetric,
    "kullback_leibler": KLDivMetric, "kldiv": KLDivMetric,
}


def create_metric(name: str, config: Config) -> Optional[Metric]:
    """reference: Metric::CreateMetric (src/metric/metric.cpp:88)."""
    name = name.strip()
    if name in ("", "none", "null", "custom", "na"):
        return None
    if name not in _METRIC_REGISTRY:
        log_warning(f"Unknown metric {name!r}; ignored")
        return None
    return _METRIC_REGISTRY[name](config)


def default_metric_for_objective(objective: str) -> str:
    """When metric is unset, the reference uses the objective's own metric
    (config.cpp Config::CheckParamConflict)."""
    return objective.split(" ")[0]
