"""Categorical best-split search (one-hot and sorted many-vs-many).

Vectorized TPU formulation of FeatureHistogram::FindBestThresholdCategoricalInner
(src/treelearner/feature_histogram.cpp:148-344):

  * one-hot mode (num_bin <= max_cat_to_onehot): left = {single category};
    every (feature, bin) candidate evaluated at once with plain lambda_l2.
  * sorted many-vs-many: categories with count >= cat_smooth are sorted by
    grad / (hess + cat_smooth); candidate left-sets are prefixes of the
    ascending and descending orders, capped at
    max_num_cat = min(max_cat_threshold, (used_bin + 1) / 2), with
    l2 -> lambda_l2 + cat_l2. Both direction scans become cumulative sums
    over the sorted histogram, evaluated for all features at once.

The reference's stepping rule is kept (feature_histogram.cpp:316): a
prefix is evaluated only once `min_data_per_group` rows have gathered
since the last evaluated one (prefixes that fail the left count/hessian
constraints add their rows but are not evaluated; the right side's
constraints end the scan). It is sequential, so it runs as a scan of at
most `max_cat_threshold` steps over the prefixes, vectorized over
features.

The chosen left-set is returned as a BIN-index bitset ([W] uint32 words);
bin 0 (the missing/other-category bin) is never selected, so missing values
fall right — matching the reference's `default_left = false` for categorical
splits (feature_histogram.cpp:155).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .split import (NEG_INF, FeatureMeta, SplitHyperParams, SplitResult,
                    leaf_gain, leaf_gain_given_output, leaf_output)

_EPS = 1e-15


class CatConfig(NamedTuple):
    """Static categorical hyperparameters (subset of Config)."""
    max_cat_to_onehot: int
    max_cat_threshold: int
    cat_l2: float
    cat_smooth: float
    min_data_per_group: float
    num_bitset_words: int       # W: ceil(num_bins_padded / 32)


def _gain_and_outputs(lg, lh, lc, rg, rh, rc, hp, parent_output,
                      leaf_min=None, leaf_max=None):
    lout = leaf_output(lg, lh, hp, lc, parent_output)
    rout = leaf_output(rg, rh, hp, rc, parent_output)
    if leaf_min is not None:
        # monotone ancestors bound every descendant's output, categorical
        # splits included (the direction rule itself only applies to
        # numerical splits)
        lout = jnp.clip(lout, leaf_min, leaf_max)
        rout = jnp.clip(rout, leaf_min, leaf_max)
    gain = (leaf_gain_given_output(lg, lh, hp, lout)
            + leaf_gain_given_output(rg, rh, hp, rout))
    return gain, lout, rout


def _group_steps(lc, ok, min_data_per_group, steps: int):
    """[F, B] bool: the prefixes the stepping rule evaluates. Prefix i is
    evaluated when it passes the constraints (``ok``) and at least
    ``min_data_per_group`` rows (``lc``: the prefixes' cumulative counts)
    have gathered since the last evaluated prefix; none past ``steps``
    (max_cat_threshold bounds the prefixes)."""
    F = lc.shape[0]

    def step(last, x):
        c, o = x
        ev = o & (c - last >= min_data_per_group)
        return jnp.where(ev, c, last), ev

    _, ev = jax.lax.scan(step, jnp.zeros((F,), lc.dtype),
                         (lc[:, :steps].T, ok[:, :steps].T))
    return jnp.pad(ev.T, ((0, 0), (0, lc.shape[1] - steps)))


def find_best_split_categorical(
    hist: jnp.ndarray,          # [3, F, B] float32 (channel-major)
    parent_sum_g: jnp.ndarray,
    parent_sum_h: jnp.ndarray,
    parent_count: jnp.ndarray,
    parent_output: jnp.ndarray,
    meta: FeatureMeta,
    hp: SplitHyperParams,
    cat: CatConfig,
    feature_mask: jnp.ndarray | None = None,
    leaf_min: jnp.ndarray | None = None,
    leaf_max: jnp.ndarray | None = None,
    cegb_pen: jnp.ndarray | None = None,      # [F] f32 CEGB gain penalty
) -> tuple[SplitResult, jnp.ndarray]:
    """Best categorical split over all features for one leaf.

    Returns (SplitResult, bin_bitset [W] uint32). gain == -inf when no
    categorical split is valid.
    """
    _, F, B = hist.shape
    W = cat.num_bitset_words
    bins = jnp.arange(B, dtype=jnp.int32)[None, :]          # [1, B]
    nb = meta.num_bins[:, None]                              # [F, 1]

    g = hist[0]
    h = hist[1]
    c = jnp.round(hist[2])

    is_cat = meta.is_categorical
    if feature_mask is not None:
        is_cat = is_cat & feature_mask
    # bin 0 is the missing/other bin (binning.py categorical layout)
    valid = (bins >= 1) & (bins < nb) & is_cat[:, None]      # [F, B]

    parent = (parent_sum_g, parent_sum_h,
              parent_count.astype(jnp.float32))
    gain_shift = leaf_gain(parent_sum_g, parent_sum_h, hp,
                           parent_count, parent_output)
    min_gain_shift = gain_shift + hp.min_gain_to_split

    hp_cat = hp._replace(lambda_l2=hp.lambda_l2 + cat.cat_l2)

    def constraints_ok(lh_, lc_, rh_, rc_, extra_right_min=0.0):
        return ((lc_ >= hp.min_data_in_leaf)
                & (rc_ >= jnp.maximum(hp.min_data_in_leaf, extra_right_min))
                & (lh_ >= hp.min_sum_hessian_in_leaf)
                & (rh_ >= hp.min_sum_hessian_in_leaf))

    # ---- one-hot candidates: left = {bin b} (fc:189-243)
    onehot_f = (meta.num_bins <= cat.max_cat_to_onehot)[:, None]  # [F, 1]
    lg1, lh1, lc1 = g, h + _EPS, c
    rg1, rh1, rc1 = (parent[0] - lg1, parent[1] - lh1 - _EPS,
                     parent[2] - lc1)
    gain1, lout1, rout1 = _gain_and_outputs(lg1, lh1, lc1, rg1, rh1, rc1,
                                            hp, parent_output,
                                            leaf_min, leaf_max)
    ok1 = valid & onehot_f & constraints_ok(lh1, lc1, rh1, rc1)
    gain1 = jnp.where(ok1 & (gain1 > min_gain_shift), gain1, NEG_INF)

    # ---- sorted many-vs-many (fc:245-343)
    include = valid & ~onehot_f & (c >= cat.cat_smooth)
    ratio = g / (h + cat.cat_smooth)
    used_bin = jnp.sum(include, axis=1)                      # [F]
    max_num_cat = jnp.minimum(cat.max_cat_threshold, (used_bin + 1) // 2)

    def direction(descending: bool):
        key = jnp.where(include, -ratio if descending else ratio, jnp.inf)
        order = jnp.argsort(key, axis=1)                     # [F, B]
        rank = jnp.argsort(order, axis=1)                    # inverse perm
        sg = jnp.take_along_axis(g, order, axis=1)
        sh = jnp.take_along_axis(h, order, axis=1)
        sc = jnp.take_along_axis(c, order, axis=1)
        lg = jnp.cumsum(sg, axis=1)
        lh = jnp.cumsum(sh, axis=1) + _EPS
        lc = jnp.cumsum(sc, axis=1)
        rg, rh, rc = (parent[0] - lg, parent[1] - lh - _EPS,
                      parent[2] - lc)
        gain, lout, rout = _gain_and_outputs(lg, lh, lc, rg, rh, rc,
                                             hp_cat, parent_output,
                                             leaf_min, leaf_max)
        pos = bins                                            # prefix length-1
        ok = ((pos < jnp.minimum(used_bin, max_num_cat)[:, None])
              & ~onehot_f & is_cat[:, None]
              & constraints_ok(lh, lc, rh, rc, cat.min_data_per_group))
        ok = ok & _group_steps(lc, ok, cat.min_data_per_group,
                               min(B, cat.max_cat_threshold))
        gain = jnp.where(ok & (gain > min_gain_shift), gain, NEG_INF)
        stats = (lg, lh, lc, rg, rh, rc, lout, rout)
        return gain, stats, rank

    gain_a, stats_a, rank_a = direction(False)
    gain_d, stats_d, rank_d = direction(True)

    stats1 = (lg1, lh1, lc1, rg1, rh1, rc1, lout1, rout1)
    all_gain = jnp.stack([gain1, gain_a, gain_d])            # [3, F, B]
    if cegb_pen is not None:
        all_gain = jnp.where(jnp.isfinite(all_gain),
                             all_gain - cegb_pen[None, :, None], all_gain)
    all_stats = [jnp.stack([a, b, d])
                 for a, b, d in zip(stats1, stats_a, stats_d)]

    flat = all_gain.reshape(-1)
    best = jnp.argmax(flat)
    best_gain = flat[best]
    kind = best // (F * B)
    f = (best // B) % F
    t = best % B

    def pick(a):
        return a[kind, f, t]

    # ---- left-set bitset over bins
    onehot_sel = bins[0] == t                                 # [B]
    rank_sel = jnp.where(kind == 1, rank_a[f], rank_d[f])     # [B]
    sorted_sel = rank_sel <= t
    selected = jnp.where(kind == 0, onehot_sel, sorted_sel)
    selected = selected & (jnp.arange(B) >= 1) & (jnp.arange(B) < nb[f, 0])
    pad = W * 32 - B
    sel_pad = jnp.pad(selected, (0, max(pad, 0)))[:W * 32]
    words = jnp.sum(
        sel_pad.reshape(W, 32).astype(jnp.uint32)
        << jnp.arange(32, dtype=jnp.uint32)[None, :], axis=1,
        dtype=jnp.uint32)

    res = SplitResult(
        gain=jnp.where(jnp.isfinite(best_gain),
                       best_gain - min_gain_shift, NEG_INF),
        feature=f.astype(jnp.int32),
        threshold=jnp.zeros((), jnp.int32),   # unused for categorical
        default_left=jnp.zeros((), bool),     # missing always falls right
        left_sum_g=pick(all_stats[0]), left_sum_h=pick(all_stats[1]),
        left_count=pick(all_stats[2]),
        right_sum_g=pick(all_stats[3]), right_sum_h=pick(all_stats[4]),
        right_count=pick(all_stats[5]),
        left_output=pick(all_stats[6]), right_output=pick(all_stats[7]),
    )
    return res, words
