"""Best-split search over feature histograms.

Vectorized TPU-native equivalent of the reference's per-feature sequential
scans (FeatureHistogram::FindBestThresholdSequentially,
src/treelearner/feature_histogram.hpp:833-1058; CUDA analog
cuda_best_split_finder.cu:776). Instead of walking bins left->right and
right->left per feature, both direction scans for ALL features are expressed
as cumulative sums over the [F, B] histogram with masking, and the best
(feature, threshold, direction) is a single argmax.

Histogram layout is channel-major [3, F, B] (channels: sum_grad, sum_hess,
count) so that every intermediate is a clean [F, B] tile with the bin axis on
the 128-wide lane dimension — cumsums and compares vectorize perfectly. The
previous [F, B, 3] layout put 3 on the minor axis, which the TPU pads to a
full lane tile (42x wasted VPU work).

Gain math follows the reference formula set (ThresholdL1 /
CalculateSplittedLeafOutput / GetLeafGainGivenOutput,
feature_histogram.hpp:712-829) including lambda_l1/l2, max_delta_step and
path_smooth; data/hessian constraints follow :877-893. It is NOT bit-exact:
per-bin counts are synthesized from hessians (`synth_count_channel` below)
and rounded on CUMULATIVE sums rather than per bin, and the bf16 Pallas
histogram path adds ~2^-9 relative hessian noise — both can flip
min_data_in_leaf decisions on bins within a row or two of the threshold.
See docs/PARITY.md for the catalogued deviations and their bounds.

Direction semantics (feature_histogram.hpp:855-1030):
 - forward scan: missing-valued rows fall RIGHT (default_left=False)
 - reverse scan: missing-valued rows fall LEFT  (default_left=True)
 - the missing bin (default_bin for MissingType::Zero, last bin for
   MissingType::NaN) is excluded from both cumulative sums; its mass reaches
   one side via `parent_total - accumulated`.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.tree import MISSING_NAN, MISSING_NONE, MISSING_ZERO

# plain python float: a module-level jnp computation would initialize the
# XLA backend at import time, breaking multi-host bring-up
# (jax.distributed.initialize must run before any backend touch)
NEG_INF = float("-inf")

# min_data_in_leaf comparison slack for the hessian-synthesized count
# channel (synth_count_channel): 0.5 is exactly the round-to-nearest
# admit region the previous `round(c) >= m` compare defined, restated
# on the unrounded channel so the tolerance is explicit (and the m-0.5
# tie resolves deterministically to "admit" instead of round-half-even).
# It must NOT be widened further: bf16 accumulation noise near one
# count spacing (0.25 below ~2^7) would then admit leaves whose true
# count is m-1 — a real min_data violation, not a rounding artifact
# (docs/PARITY.md "synthesized-count tolerance").
SYNTH_COUNT_SLACK = 0.5


def expand_feature_offset_hist(flat: jnp.ndarray, offsets: tuple,
                               widths: tuple, num_bins: int) -> jnp.ndarray:
    """Ragged per-feature-offset histogram -> uniform [..., F, num_bins]
    grid for the split scans below.

    `flat` is [..., total] where feature f owns the `widths[f]` columns
    starting at `offsets[f]` (the reference's FeatureGroupOffsets layout;
    see ops/histogram_tiered.py). Bins a feature does not own gather the
    fill value 0 — they can hold no mass by construction, so the
    cumulative forward/reverse scans and every gain formula are
    unchanged. The same OOB-fill gather as the EFB bundle expansion
    (models/gbdt.py bundle_expand)."""
    offs = np.asarray(offsets, dtype=np.int32)[:, None]
    wid = np.asarray(widths, dtype=np.int32)[:, None]
    b = np.arange(num_bins, dtype=np.int32)[None, :]
    idx = np.where(b < wid, offs + b, np.int32(-1))       # [F, num_bins]
    return jnp.take(flat, jnp.asarray(idx), axis=-1,
                    mode="fill", fill_value=0)


class SplitHyperParams(NamedTuple):
    """Static split hyperparameters (subset of Config used by the finder)."""
    min_data_in_leaf: float
    min_sum_hessian_in_leaf: float
    lambda_l1: float
    lambda_l2: float
    max_delta_step: float
    min_gain_to_split: float
    path_smooth: float


class FeatureMeta(NamedTuple):
    """Per-feature metadata device arrays (reference: FeatureMetainfo,
    feature_histogram.hpp:30)."""
    num_bins: jnp.ndarray       # [F] int32 (includes NaN bin if present)
    missing_type: jnp.ndarray   # [F] int32
    default_bin: jnp.ndarray    # [F] int32
    is_categorical: jnp.ndarray  # [F] bool
    monotone: Optional[jnp.ndarray] = None  # [F] int8: -1/0/+1 constraint
    inter_sets: Optional[jnp.ndarray] = None  # [S, F] bool: interaction
    #                                           constraint set membership
    bundle_expand: Optional[jnp.ndarray] = None  # [F*B] i32: EFB bundle-
    #   histogram -> per-feature histogram gather map (OOB = fill 0)
    bundle_mfb: Optional[jnp.ndarray] = None     # [F, B] f32 one-hot of
    #   each feature's default bin (FixHistogram reconstruction)
    forced: Optional[jnp.ndarray] = None  # [4, S] i32 forced-split tree in
    #   BFS order: rows (feature, bin_threshold, left_child, right_child);
    #   children are forced-node ids or -1 (forcedsplits_filename,
    #   serial_tree_learner.cpp:628)
    cegb_coupled: Optional[jnp.ndarray] = None  # [F] f32 per-feature
    #   coupled penalty (cegb_penalty_feature_coupled mapped to inner
    #   features; cost_effective_gradient_boosting.hpp:87)


class SplitResult(NamedTuple):
    """Best split for one leaf (reference: SplitInfo,
    src/treelearner/split_info.hpp)."""
    gain: jnp.ndarray           # f32 scalar; -inf when no valid split
    feature: jnp.ndarray        # i32 inner feature index
    threshold: jnp.ndarray      # i32 bin threshold (left: bin <= threshold)
    default_left: jnp.ndarray   # bool
    left_sum_g: jnp.ndarray
    left_sum_h: jnp.ndarray
    left_count: jnp.ndarray
    right_sum_g: jnp.ndarray
    right_sum_h: jnp.ndarray
    right_count: jnp.ndarray
    left_output: jnp.ndarray
    right_output: jnp.ndarray


def threshold_l1(s, l1):
    """reference: feature_histogram.hpp:712."""
    reg = jnp.maximum(0.0, jnp.abs(s) - l1)
    return jnp.sign(s) * reg


def synth_count_channel(hist2: jnp.ndarray, count, sum_h) -> jnp.ndarray:
    """[2, F, B] (grad, hess) histogram -> [3, F, B] with the count channel
    synthesized from hessians via the reference's cnt_factor: the reference
    histogram entry is (grad, hess) only (bin.h:40 kHistEntrySize) and split
    search derives per-bin counts as RoundInt(hess * num_data / sum_hessian)
    (FindBestThresholdSequentially, feature_histogram.hpp:529,844). The
    rounding happens on the cumulative sums inside _numeric_gain_map."""
    cntf = count / jnp.maximum(sum_h, 1e-12)
    return jnp.concatenate([hist2, hist2[1:2] * cntf], axis=0)


def _newton_step(sum_g, sum_h, hp: SplitHyperParams):
    return -threshold_l1(sum_g, hp.lambda_l1) / (sum_h + hp.lambda_l2)


def root_totals(hist_root: jnp.ndarray, cnt_row: jnp.ndarray,
                hp: SplitHyperParams, psum, scale=None, owner=None):
    """The root's (sum_g, sum_h, count, output): the ONE place that says
    where a node's totals come from, for all three growers.

    `hist_root` is the root histogram [2, columns, B] as the kernels
    built it and the data-parallel exchange summed it, before any
    per-feature re-slicing (EFB's bundle_expand). Every in-bag row falls
    into exactly one bin of a storage column, so the bins of column 0 sum
    to the node's gradient and hessian totals AS THE HISTOGRAM HOLDS
    THEM: operands rounded to bfloat16 on the Pallas path
    (histogram_pallas._make_W), discretized to int8 under quantized
    gradients (int32 bins, summed exactly and then descaled by `scale`
    [2], as GradientDiscretizer's leaf sums are), float32 on the portable
    path. Every child's totals descend as "parent minus the histogram's
    left sum" (_numeric_gain_map), so a root total from anywhere else (a
    float32 sum of the unrounded rows, say) leaves its whole difference in
    the one leaf at the end of the chain of complement children
    (docs/PERF.md, Operands and totals).

    The count is no histogram channel: it stays the exact number of
    in-bag rows (`cnt_row` is 0/1), summed over the shards by `psum`.
    `owner` (a traced bool) is given only where the exchange left each
    rank a feature SLICE of the root histogram (grow.py's reduce-scatter
    ownership): column 0 is whole on the rank whose slice starts at
    feature 0, and its totals ride to the others in the count's psum;
    x + 0 + ... + 0 is exact, so every rank holds the owner's bits."""
    tot = jnp.sum(hist_root[:, 0, :], axis=-1)                   # [2]
    if scale is not None:
        tot = tot.astype(jnp.float32) * scale
    cnt = jnp.sum(cnt_row)
    if owner is None:
        root_c = psum(cnt)
    else:
        shared = psum(jnp.append(jnp.where(owner, tot, 0.0), cnt))
        tot, root_c = shared[:2], shared[2]
    root_out = jnp.asarray(_newton_step(tot[0], tot[1], hp), jnp.float32)
    return tot[0], tot[1], root_c, root_out


def leaf_output(sum_g, sum_h, hp: SplitHyperParams, num_data, parent_output):
    """reference: CalculateSplittedLeafOutput (feature_histogram.hpp:718)."""
    ret = _newton_step(sum_g, sum_h, hp)
    if hp.max_delta_step > 0:
        ret = jnp.clip(ret, -hp.max_delta_step, hp.max_delta_step)
    if hp.path_smooth > 1e-15:
        n_over_s = num_data / hp.path_smooth
        ret = ret * n_over_s / (n_over_s + 1.0) \
            + parent_output / (n_over_s + 1.0)
    return ret


def leaf_gain_given_output(sum_g, sum_h, hp: SplitHyperParams, output):
    """reference: GetLeafGainGivenOutput (feature_histogram.hpp:818)."""
    sg = threshold_l1(sum_g, hp.lambda_l1)
    return -(2.0 * sg * output + (sum_h + hp.lambda_l2) * output * output)


def leaf_gain(sum_g, sum_h, hp: SplitHyperParams, num_data, parent_output):
    """reference: GetLeafGain (feature_histogram.hpp:800)."""
    out = leaf_output(sum_g, sum_h, hp, num_data, parent_output)
    return leaf_gain_given_output(sum_g, sum_h, hp, out)


def _numeric_gain_map(hist, parent_sum_g, parent_sum_h, parent_count,
                      parent_output, meta, hp, feature_mask, leaf_min,
                      leaf_max):
    """Numerical split-gain map shared by the best-split argmax and the
    voting-parallel per-feature ranking: returns
    (gain [2, F, B] with -inf where invalid/below min-gain, ok mask,
    (lg, lh, lc, rg, rh, rc, lout, rout) stat maps, min_gain_shift)."""
    _, F, B = hist.shape
    bins = jnp.arange(B, dtype=jnp.int32)[None, :]          # [1, B]
    nb = meta.num_bins[:, None]                              # [F, 1]

    valid_bin = bins < nb
    # the bin whose rows are "missing" for direction purposes
    missing_bin = jnp.where(
        meta.missing_type == MISSING_NAN, meta.num_bins - 1,
        jnp.where(meta.missing_type == MISSING_ZERO, meta.default_bin, -1))
    excl = (bins == missing_bin[:, None]) | ~valid_bin       # [F, B]

    acc = jnp.where(excl[None, :, :], 0.0, hist)             # [3, F, B]
    cum = jnp.cumsum(acc, axis=-1)                           # [3, F, B]
    acc_tot = cum[:, :, -1:]                                 # [3, F, 1]

    parent = jnp.stack([parent_sum_g, parent_sum_h,
                        parent_count.astype(jnp.float32)])   # [3]
    miss = parent[:, None, None] - acc_tot                   # [3, F, 1]

    # threshold t: left = bins <= t.
    # dir 0 (forward scan): left = cum[t];       missing right
    # dir 1 (reverse scan): left = cum[t]+miss;  missing left
    # stacked as [3, 2, F, B]
    left = jnp.stack([cum, cum + miss], axis=1)
    right = parent[:, None, None, None] - left

    lg, lh, lc = left[0], left[1], jnp.round(left[2])        # [2, F, B]
    rg, rh, rc = right[0], right[1], jnp.round(right[2])
    # min_data_in_leaf screening runs on the UNROUNDED synthesized
    # channel with SYNTH_COUNT_SLACK: >= m - 0.5 is exactly the
    # round-to-nearest admit region the rounded compare had, so a leaf
    # whose exact count meets the threshold is not rejected for
    # synthesizing a hair under it, while one short by a full row stays
    # rejected (docs/PARITY.md "synthesized-count tolerance")
    lc_ok = left[2] >= hp.min_data_in_leaf - SYNTH_COUNT_SLACK
    rc_ok = right[2] >= hp.min_data_in_leaf - SYNTH_COUNT_SLACK

    # threshold validity (scan ranges, feature_histogram.hpp:860-944):
    # t in [0, num_bin-2]; for the reverse scan of a NaN-missing feature the
    # last non-NaN threshold is num_bin-3 (the NaN bin is not walked)
    max_t = nb - 2                                            # [F, 1]
    max_t_r = jnp.where((meta.missing_type == MISSING_NAN)[:, None],
                        nb - 3, max_t)
    t_ok_f = bins <= max_t
    t_ok_r = bins <= max_t_r
    # for MissingType::Zero the threshold bin equal to the default bin is
    # skipped (its left-sum equals the previous bin's; skipping matches the
    # reference exactly and avoids duplicate thresholds)
    skip_default = (meta.missing_type == MISSING_ZERO)[:, None] & \
        (bins == meta.default_bin[:, None])
    t_ok = jnp.stack([t_ok_f & ~skip_default, t_ok_r & ~skip_default],
                     axis=0)

    ok = (t_ok
          & lc_ok & rc_ok
          & (lh >= hp.min_sum_hessian_in_leaf)
          & (rh >= hp.min_sum_hessian_in_leaf))
    if feature_mask is not None:
        ok = ok & feature_mask[None, :, None]
    ok = ok & ~meta.is_categorical[None, :, None]

    lout = leaf_output(lg, lh, hp, lc, parent_output)
    rout = leaf_output(rg, rh, hp, rc, parent_output)
    if leaf_min is not None:
        lout = jnp.clip(lout, leaf_min, leaf_max)
        rout = jnp.clip(rout, leaf_min, leaf_max)
    if meta.monotone is not None:
        mono = meta.monotone[None, :, None]
        ok = ok & ~(((mono > 0) & (lout > rout))
                    | ((mono < 0) & (lout < rout)))
    gain = (leaf_gain_given_output(lg, lh, hp, lout)
            + leaf_gain_given_output(rg, rh, hp, rout))

    # gain_shift: gain of not splitting (BeforeNumerical,
    # feature_histogram.hpp:199-208)
    gain_shift = leaf_gain(parent_sum_g, parent_sum_h, hp,
                           parent_count, parent_output)
    min_gain_shift = gain_shift + hp.min_gain_to_split
    return gain, ok, (lg, lh, lc, rg, rh, rc, lout, rout), min_gain_shift


def per_feature_best_gain(
    hist: jnp.ndarray,          # [3, F, B]
    parent_sum_g: jnp.ndarray,
    parent_sum_h: jnp.ndarray,
    parent_count: jnp.ndarray,
    parent_output: jnp.ndarray,
    meta: FeatureMeta,
    hp: SplitHyperParams,
    feature_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """[F] best numerical split gain per feature (-inf where none valid):
    the local ranking signal for the voting-parallel learner's top-k
    proposal (PV-Tree local voting, voting_parallel_tree_learner.cpp)."""
    gain, ok, _, min_gain_shift = _numeric_gain_map(
        hist, parent_sum_g, parent_sum_h, parent_count, parent_output,
        meta, hp, feature_mask, None, None)
    gain = jnp.where(ok & (gain > min_gain_shift), gain, NEG_INF)
    return jnp.max(gain, axis=(0, 2)) - min_gain_shift


def find_best_split(
    hist: jnp.ndarray,          # [3, F, B] float32: (sum_g, sum_h, count)
    parent_sum_g: jnp.ndarray,  # scalar
    parent_sum_h: jnp.ndarray,
    parent_count: jnp.ndarray,
    parent_output: jnp.ndarray,
    meta: FeatureMeta,
    hp: SplitHyperParams,
    feature_mask: jnp.ndarray | None = None,  # [F] bool (col sampling)
    leaf_min: jnp.ndarray | None = None,      # scalar: monotone lower bound
    leaf_max: jnp.ndarray | None = None,      # scalar: monotone upper bound
    forced_f: jnp.ndarray | None = None,      # scalar i32: forced feature
    forced_b: jnp.ndarray | None = None,      # scalar i32: forced threshold
    cegb_pen: jnp.ndarray | None = None,      # [F] f32: CEGB gain penalty
    rand_bins: jnp.ndarray | None = None,     # [F] i32: extra_trees random
    #   threshold per feature — only this bin is considered
    mono_pen_factor: jnp.ndarray | None = None,  # scalar: monotone_penalty
    #   gain multiplier for splits on monotone features
    #   (ComputeMonotoneSplitGainPenalty, monotone_constraints.hpp:358)
) -> SplitResult:
    """Best numerical split over all features for one leaf.

    Returns gain == -inf when no split satisfies the constraints. Categorical
    features are handled by `find_best_split_categorical` (ops/categorical.py)
    and masked out here.

    Monotone constraints follow the reference's "basic" method
    (BasicConstraint / LeafConstraintsBase::Create,
    monotone_constraints.hpp:330): child outputs are clamped into the
    leaf's [leaf_min, leaf_max] bounds inherited from monotone ancestors,
    and splits on a +-1 monotone feature whose (clamped) child outputs
    violate the direction are rejected.
    """
    (gain, ok, stats, min_gain_shift) = _numeric_gain_map(
        hist, parent_sum_g, parent_sum_h, parent_count, parent_output,
        meta, hp, feature_mask, leaf_min, leaf_max)
    lg, lh, lc, rg, rh, rc, lout, rout = stats
    _, F, B = hist.shape
    bins = jnp.arange(B, dtype=jnp.int32)[None, :]          # [1, B]

    if forced_f is not None:
        # forced-split mode (SerialTreeLearner::ForceSplits,
        # serial_tree_learner.cpp:628): the (feature, threshold) pair is
        # fixed — only the missing direction is chosen — and the
        # min-gain bar does not apply (a forced split lands even with
        # negative gain; only the data/hessian constraints hold)
        restrict = ((jnp.arange(F, dtype=jnp.int32) == forced_f)[:, None]
                    & (bins == forced_b))
        gain = jnp.where(ok & restrict[None, :, :], gain, NEG_INF)
    else:
        gain = jnp.where(ok & (gain > min_gain_shift), gain, NEG_INF)
    if rand_bins is not None:
        # extra_trees (Config::extra_trees): each feature offers ONE
        # uniformly drawn threshold per search (BeforeNumerical draws
        # rand.NextInt(0, num_bin - 2), feature_histogram.hpp:203-207;
        # the scan then skips every other threshold)
        gain = jnp.where((bins == rand_bins[:, None])[None, :, :],
                         gain, NEG_INF)
    if cegb_pen is not None:
        # CEGB: per-feature gain penalty subtracted AFTER each feature's
        # best-threshold scan, before the cross-feature argmax — the
        # penalized gain is the stored one (DeltaGain applied at
        # serial_tree_learner.cpp FindBestSplitsFromHistograms)
        gain = jnp.where(jnp.isfinite(gain),
                         gain - cegb_pen[None, :, None], gain)
    if mono_pen_factor is not None and meta.monotone is not None:
        # monotone_penalty multiplies the FINAL (shifted) gain of splits
        # on monotone features (serial_tree_learner.cpp:1001-1005);
        # applied in map space as an affine transform around the shift
        mono_f = (meta.monotone != 0)[None, :, None]
        gain = jnp.where(
            mono_f & jnp.isfinite(gain),
            (gain - min_gain_shift) * mono_pen_factor + min_gain_shift,
            gain)

    return _pick_best(gain, stats, F, B, min_gain_shift)


def _pick_best(gain, stats, F, B, min_gain_shift):
    """Argmax over a filtered [2, F, B] gain map + exact stat selection."""
    lg, lh, lc, rg, rh, rc, lout, rout = stats
    flat = gain.reshape(-1)
    best = jnp.argmax(flat)
    best_gain = flat[best]
    d = best // (F * B)
    f = (best // B) % F
    t = best % B

    # pick per-split stats with a one-hot dot (exact: single 1.0 product).
    # A stacked [8, 2, F, B] gather materializes ~117MB + relayout copies
    # when vmapped over a 256-leaf wave; the one-hot contraction fuses.
    onehot = (jnp.arange(2 * F * B, dtype=jnp.int32) == best
              ).astype(jnp.float32)

    def pick(x):
        # non-selected entries may be inf/NaN (e.g. division by zero-hess
        # bins); 0.0 * inf = NaN would poison the contraction. HIGHEST
        # precision: the TPU default would round the picked value to bf16.
        xf = x.reshape(-1)
        return jnp.dot(jnp.where(jnp.isfinite(xf), xf, 0.0), onehot,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)

    picked = [pick(x) for x in (lg, lh, lc, rg, rh, rc, lout, rout)]

    return SplitResult(
        gain=jnp.where(jnp.isfinite(best_gain),
                       best_gain - min_gain_shift, NEG_INF),
        feature=f.astype(jnp.int32),
        threshold=t.astype(jnp.int32),
        default_left=(d == 1),
        left_sum_g=picked[0], left_sum_h=picked[1], left_count=picked[2],
        right_sum_g=picked[3], right_sum_h=picked[4], right_count=picked[5],
        left_output=picked[6], right_output=picked[7],
    )


def find_best_split_and_forced(
    hist, parent_sum_g, parent_sum_h, parent_count, parent_output,
    meta: FeatureMeta, hp: SplitHyperParams,
    feature_mask: jnp.ndarray | None,
    leaf_min, leaf_max,
    forced_f: jnp.ndarray, forced_b: jnp.ndarray,
    cegb_pen: jnp.ndarray | None = None,
    rand_bins: jnp.ndarray | None = None,
    mono_pen_factor: jnp.ndarray | None = None,
) -> tuple[SplitResult, SplitResult]:
    """Best numerical split AND the fixed forced-(feature, threshold)
    split from ONE gain-map computation (the map is the expensive part;
    the forced cell is just a different selection mask). The column
    sampler applies only to the normal selection — forced splits bypass
    it (ForceSplits, serial_tree_learner.cpp:628)."""
    gain, ok, stats, min_gain_shift = _numeric_gain_map(
        hist, parent_sum_g, parent_sum_h, parent_count, parent_output,
        meta, hp, None, leaf_min, leaf_max)
    _, F, B = hist.shape
    bins = jnp.arange(B, dtype=jnp.int32)[None, :]
    ok_n = ok if feature_mask is None else (ok & feature_mask[None, :, None])
    gain_n = jnp.where(ok_n & (gain > min_gain_shift), gain, NEG_INF)
    if rand_bins is not None:
        # extra_trees applies only to the NORMAL selection; a forced
        # split keeps its fixed threshold
        gain_n = jnp.where((bins == rand_bins[:, None])[None, :, :],
                           gain_n, NEG_INF)
    if cegb_pen is not None:
        gain_n = jnp.where(jnp.isfinite(gain_n),
                           gain_n - cegb_pen[None, :, None], gain_n)
    if mono_pen_factor is not None and meta.monotone is not None:
        mono_f = (meta.monotone != 0)[None, :, None]
        gain_n = jnp.where(
            mono_f & jnp.isfinite(gain_n),
            (gain_n - min_gain_shift) * mono_pen_factor + min_gain_shift,
            gain_n)
    restrict = ((jnp.arange(F, dtype=jnp.int32) == forced_f)[:, None]
                & (bins == forced_b))
    gain_f = jnp.where(ok & restrict[None, :, :], gain, NEG_INF)
    return (_pick_best(gain_n, stats, F, B, min_gain_shift),
            _pick_best(gain_f, stats, F, B, min_gain_shift))
