"""The wave grower's production Pallas kernels, end to end through
``lgb.train``, against the portable XLA path.

On a TPU ``grow_tree_wave`` (ops/grow_wave.py) runs one wave megakernel
pass per wave at F <= 32 (``wave_pass_pallas`` / ``wave_relabel_pallas``)
and past it ``wave_decide_pallas`` (``wave_apply_pallas`` on EFB
tables) plus the F-gridded slot kernels. The rest
of tier-1 trains on the CPU, where ``_use_pallas`` is false and every one
of those is replaced by its XLA fall-back, so this file is where the
kernels the chip runs meet the trainer: each case trains the same seeded
data twice with ``histogram_impl=auto``, once with
``LIGHTGBM_TPU_PALLAS_INTERPRET=1`` (every kernel under the Pallas
interpreter) and once without (the portable path, which shares no code
with the kernels), and compares the two models' predictions.

* ``use_quantized_grad``: int8 operands and int32 accumulation are exact
  in both arms, so the predictions must be bit-identical.
* float32: the Pallas arm feeds the MXU bfloat16 operands, the XLA arm
  float32, so histograms differ in the last bits and a near-tie between
  two splits can flip. A bare tolerance on predictions does not survive
  that (one flipped split moves a whole leaf's rows), so the float cases
  hold two statistics that do, and that a broken kernel still fails
  (``test_planted_fault_is_seen``): the median over the rows of the gap
  between the two models' predictions (a flipped split moves less than
  half of the rows), and the relative gap of their training loss (two
  near-tied splits gain nearly the same). Readings are listed beside the
  limits below.

ROADMAP A1, closed by PR 34: a node's totals had two owners, the root's
a float32 sum of the rows as they are and the histogram's bins a sum of
the rows as ``_make_W`` rounds them to bfloat16, and every child's totals
descend as "parent minus the histogram's left sum", so the difference of
the two root sums landed whole in the one leaf at the end of the chain of
complement children. The cases above cannot show that (a hessian of 1 is
exact in bfloat16, and one round never sees a rounded hessian), so
``test_leaf_values_follow_their_histograms`` holds it: binary log-loss,
three rounds, every leaf value against the sums of its own rows. Since
PR 34 the root's totals are read off the root histogram
(``ops/split.py:root_totals``) and the float limits below were tightened
from a fresh table; what they still leave room for is the rounding of
each row's gradient, which is the chip's arithmetic and no fault.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb

INTERP = "LIGHTGBM_TPU_PALLAS_INTERPRET"
# one round, learning rate 0.1: predictions of spread 0.10-0.28. Binning
# stays on the host in both arms (the bucketize kernel has its own parity
# suite, and compiling it interpreted costs 2 s a case).
BASE = {"objective": "regression", "num_leaves": 5, "max_bin": 31,
        "min_data_in_leaf": 5, "verbose": -1, "deterministic": True,
        "histogram_impl": "auto", "binning_impl": "host"}

MEGA = {"wave_pass_pallas", "wave_relabel_pallas"}
# past 32 columns (ops/grow_wave.py wave_route) every unbundled table,
# categorical or not, decides each row in the kernel; only EFB storage
# keeps the [128, N] matrix (wave_apply_pallas)
WIDE = {"wave_decide_pallas", "build_histogram_slots_pallas"}
ROUTE_KERNELS = {"wave_decide_pallas", "wave_apply_pallas"}
# regime -> (F, rows, params over BASE, categorical columns, NaN share,
#            the kernels the interpreted arm must call).
# Two regimes grow 15 leaves, the rest 5 (wave buckets 1, 2, 4): a case
# is two compiles of the grower, 5 s each and all of its time, and the
# file has to stay under 200 s (324 s at 15 leaves throughout and 255
# columns, 203 s at 7 leaves, 186 s as it stands; my CPU runs, PR 31).
# For the same reason the 255-bin regime has 72 columns, not 255: at 255
# the portable arm alone compiles for 27 s.
REGIMES = {
    # the wave megakernel at Higgs's width and bin count
    "f28_b63_megakernel": (28, 400, {"max_bin": 63, "num_leaves": 15},
                           (), 0.0, MEGA),
    # in-kernel decision + F-gridded slots: one column past the 32
    # (at 15 leaves, where one near-tie split flips in the float case),
    # two full feature tiles, three tiles and a 4-column tail
    "f33": (33, 400, {"num_leaves": 15}, (), 0.0, WIDE),
    "f64": (64, 400, {}, (), 0.0, WIDE),
    "f100": (100, 400, {}, (), 0.0, WIDE),
    # the hi/lo decomposition of the 256-lane bin axis, two tiles + tail
    "f72_b255_hilo": (72, 300, {"max_bin": 255}, (), 0.0, WIDE),
    "monotone_basic": (40, 400, {
        "monotone_constraints": [1, -1] * 20,
        "monotone_constraints_method": "basic"}, (), 0.0, WIDE),
    "interaction_sets": (40, 400, {"interaction_constraints": [
        list(range(0, 14)), list(range(10, 26)), list(range(24, 40))]},
        (), 0.0, WIDE),
    "categorical": (40, 400, {"max_cat_to_onehot": 4,
                              "max_cat_threshold": 16},
                    (0, 3, 7, 11), 0.0, WIDE),
    # a categorical table on the megakernel at 256 bins: bitsets of 8
    # words, a column of 250 categories, some past the 99 % cut in bin 0
    "cat_f8_b255_megakernel": (8, 3000, {
        "max_bin": 63, "num_leaves": 15, "min_data_per_group": 5,
        "cat_smooth": 1.0}, (0, (5, 250)), 0.0, MEGA),
    "nan_bagging": (40, 400, {"bagging_fraction": 0.7, "bagging_freq": 1,
                              "bagging_seed": 5}, (), 0.1, WIDE),
    # a row-wise histogram pin at F <= 32 leaves the megakernel (its
    # histogram is col-wise only): the in-kernel decision moves the rows
    "f28_rowwise": (28, 400, {"histogram_impl": "rowwise"}, (), 0.0,
                    {"wave_decide_pallas"}),
}

# Float limits, and what was read (my CPU runs, PR 34; the table below).
# Before the root's totals came off the root histogram they stood at
# 2e-4 and 2e-3 over readings of at most 3.15e-5 and 5.35e-5 (PR 31).
# The planted fault reads 3.2e-2 and 7.8e-2 (test_planted_fault_is_seen).
MEDIAN_GAP_LIMIT = 1e-4     # read: at most 3.15e-5 (categorical)
LOSS_GAP_LIMIT = 2e-4       # read: at most 3.67e-5 (f100)


def _data(F, n, cat_cols, nan_share, seed=3):
    """``cat_cols``: a column of 9 categories, or (column, categories)."""
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    for c in cat_cols:
        c, k = c if isinstance(c, tuple) else (c, 9)
        X[:, c] = rng.randint(0, k, size=n)
    y = (X[:, 0] - 0.5 * X[:, F // 2] + np.sin(X[:, 1])).astype(np.float32)
    for c in cat_cols:
        if isinstance(c, tuple):        # the categories' own effects
            y += rng.normal(size=c[1])[X[:, c[0]].astype(np.int64)]
    if nan_share:
        X[rng.uniform(size=X.shape) < nan_share] = np.nan
    return X, y


KERNELS = ("wave_pass_pallas", "wave_relabel_pallas", "wave_apply_pallas",
           "wave_decide_pallas", "build_histogram_slots_pallas")


def _kernel_spy(monkeypatch, fault=None):
    """Wrap the wave grower's Pallas entry points (ops/histogram_pallas.py)
    so the set of those the trainer called is known; ``fault(name, out)``
    may replace what one returns."""
    from lightgbm_tpu.ops import histogram_pallas, histogram_tiered
    seen = set()
    for fn in KERNELS:
        def spy(*a, _fn=fn, _real=getattr(histogram_pallas, fn), **kw):
            seen.add(_fn)
            out = _real(*a, **kw)
            return fault(_fn, out) if fault else out
        for mod in (histogram_pallas, histogram_tiered):
            if hasattr(mod, fn):
                monkeypatch.setattr(mod, fn, spy)
    return seen


_PORTABLE = {}      # (regime, quantized) -> the portable arm's predictions


def _train_both(regime, quantized, monkeypatch, fault=None):
    """(interpreted-kernel predictions, portable predictions, labels,
    the kernels the interpreted arm called)."""
    F, n, extra, cat_cols, nan_share, _ = REGIMES[regime]
    X, y = _data(F, n, cat_cols, nan_share)
    params = dict(BASE, use_quantized_grad=bool(quantized), **extra)
    seen = _kernel_spy(monkeypatch, fault)

    def train():
        cats = [c[0] if isinstance(c, tuple) else c for c in cat_cols]
        ds = lgb.Dataset(X, label=y, categorical_feature=cats) \
            if cats else lgb.Dataset(X, label=y)
        return lgb.train(params, ds, num_boost_round=1).predict(X)

    monkeypatch.setenv(INTERP, "1")
    interpreted = train()
    kernels = set(seen)
    monkeypatch.delenv(INTERP)
    if (regime, quantized) not in _PORTABLE:
        seen.clear()
        _PORTABLE[regime, quantized] = train()
        assert not seen, f"the portable arm called Pallas kernels: {seen}"
    return interpreted, _PORTABLE[regime, quantized], y, kernels


def _float_gaps(a, b, y):
    """(median over rows of |a - b|, relative gap of the training loss)."""
    la, lb = float(np.mean((a - y) ** 2)), float(np.mean((b - y) ** 2))
    return float(np.median(np.abs(a - b))), abs(la - lb) / lb


# The float cases' readings (my CPU runs, PR 34): max |gap| and the share
# of rows apart by over 1e-3, which a bare tolerance would have been held
# to (f33's flipped split is why it is not), then the two that are held.
#   regime                 max gap   over 1e-3   median gap   loss gap
#   f28_b63_megakernel     1.42e-04    0.0000     1.17e-05    3.65e-05
#   f33                    5.88e-02    0.1100     2.28e-05    4.90e-06
#   f64                    4.01e-05    0.0000     3.39e-06    3.04e-05
#   f100                   6.56e-05    0.0000     1.34e-05    3.67e-05
#   f72_b255_hilo          9.87e-05    0.0000     2.97e-05    9.43e-06
#   monotone_basic         7.01e-05    0.0000     1.86e-05    2.67e-06
#   interaction_sets       5.25e-05    0.0000     2.29e-06    8.53e-07
#   categorical            8.90e-05    0.0000     3.15e-05    2.85e-05
#   nan_bagging            5.54e-05    0.0000     1.68e-05    1.85e-05
#   f28_rowwise            3.07e-05    0.0000     1.63e-05    1.32e-05  (PR 38)


@pytest.mark.parametrize("grad", ["quantized", "float"])
@pytest.mark.parametrize("regime", list(REGIMES))
def test_interpreted_wave_matches_portable(regime, grad, monkeypatch):
    a, b, y, kernels = _train_both(regime, grad == "quantized", monkeypatch)
    assert REGIMES[regime][5] <= kernels, kernels
    # one route a table: the other row-moving kernel is never called
    assert kernels & ROUTE_KERNELS <= REGIMES[regime][5], kernels
    assert np.std(b) > 0.05            # the tree was grown, not a stump
    if grad == "quantized":
        np.testing.assert_array_equal(a, b)
        return
    median_gap, loss_gap = _float_gaps(a, b, y)
    detail = (f"median gap {median_gap:.3e}, loss gap {loss_gap:.3e}, max "
              f"gap {np.max(np.abs(a - b)):.3e}, rows over 1e-3 "
              f"{np.mean(np.abs(a - b) > 1e-3):.4f}")
    assert median_gap < MEDIAN_GAP_LIMIT and loss_gap < LOSS_GAP_LIMIT, detail


def test_planted_fault_is_seen(monkeypatch):
    """The limits bite: with bin 61 of every wave histogram the megakernel
    returns zeroed, the quantized case is no longer bit-identical and the
    float case is over both limits (median gap 3.2e-2, loss gap 7.8e-2; my
    CPU run, PR 31). A LOW bin would not do for the float case: the search
    accumulates from the high bins down and takes the other side from the
    parent's totals, so zeroing bin 3 leaves every float reading where it
    was, and bins 10 and 20 move the median gap by under 2e-5 (my CPU
    scan of bins 3 to 61, PR 31)."""
    def fault(name, out):
        if name == "wave_pass_pallas":
            return (out[0], out[1].at[..., 61].set(0)) + tuple(out[2:])
        return out

    regime = "f28_b63_megakernel"
    a, b, _, kernels = _train_both(regime, True, monkeypatch, fault)
    assert "wave_pass_pallas" in kernels
    assert not np.array_equal(a, b)
    a, b, y, _ = _train_both(regime, False, monkeypatch, fault)
    median_gap, loss_gap = _float_gaps(a, b, y)
    assert median_gap > MEDIAN_GAP_LIMIT and loss_gap > LOSS_GAP_LIMIT, \
        (median_gap, loss_gap)


# ---- a node's totals and its histogram's bins are sums of the same values
TOTALS_PARAMS = dict(BASE, objective="binary", boost_from_average=False,
                     max_bin=63, num_leaves=15, learning_rate=0.1)
TOTALS_ROWS, TOTALS_ROUNDS = 2000, 3
# Readings (my CPU runs, PR 34; seeds 1 to 4, the widest of the three
# trees). Under the interpreter the parent commit, whose root totals
# were float32 sums of the rows, read 1.08e-2, 3.82e-3, 5.46e-3, 4.08e-3
# (2,000 rows are enough: at 20,000 x 31 leaves 9.0e-3 and 7.8e-3, and
# PR 33 read 1.1e-2 to 2.0e-2 on three of four seeds at 200,000); this
# tree reads 5.0e-8, 4.9e-8, 4.1e-8, 4.3e-8. The portable arm reads
# 8.3e-7 on both. The first tree reads 5e-8 on the parent too (its
# gradients, +-0.5 and 0.25, are exact in bfloat16): the damage came
# with the second tree's hessians, which lie just under 0.25 and which
# bfloat16 mostly rounds up to it.
LEAF_VALUE_GAP_LIMIT = 1e-4


def _leaf_value_gap(bst, X, y, operand_dtype):
    """``leaf_value_gap`` as bench/compare.py defines it, the reference
    being told the program's own partition and operands: for each tree
    the widest |leaf value - (-lr * sum_g / sum_h over the leaf's rows)|
    over the larger of that step and the tree's median step; the sums in
    float64, of gradients rounded as the arm's histogram rounds them."""
    leaf = bst.predict(X, pred_leaf=True).reshape(len(X), -1)
    worst = 0.0
    for t, tree in enumerate(bst._gbdt.models):
        s = bst.predict(X, raw_score=True, num_iteration=t) if t \
            else np.zeros(len(X))
        p = 1.0 / (1.0 + np.exp(-s))
        g, h = ((v.astype(np.float32).astype(operand_dtype)
                 .astype(np.float64)) for v in (p - y, p * (1.0 - p)))
        n = tree.num_leaves
        step = -TOTALS_PARAMS["learning_rate"] \
            * np.bincount(leaf[:, t], weights=g, minlength=n) \
            / np.bincount(leaf[:, t], weights=h, minlength=n)
        scale = np.maximum(np.abs(step), np.median(np.abs(step)))
        worst = max(worst, float(np.max(
            np.abs(tree.leaf_value[:n] - step) / scale)))
    return worst


@pytest.mark.parametrize("arm,seed", [
    ("interpreted", 1), ("interpreted", 2), ("interpreted", 3),
    ("interpreted", 4), ("portable", 1)])
def test_leaf_values_follow_their_histograms(arm, seed, monkeypatch):
    """The wave megakernel at Higgs's 28 columns and 63 bins, binary
    log-loss, three rounds: every leaf's value is that of the sums of
    its own rows as the histogram holds them (bfloat16 operands under
    the interpreter, float32 on the portable path)."""
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(TOTALS_ROWS, 28)).astype(np.float32)
    y = (X @ rng.normal(size=28) + rng.normal(
        scale=0.5 * np.sqrt(28), size=TOTALS_ROWS) > 0).astype(np.float32)
    seen = _kernel_spy(monkeypatch)
    if arm == "interpreted":
        monkeypatch.setenv(INTERP, "1")
    bst = lgb.train(TOTALS_PARAMS, lgb.Dataset(X, label=y),
                    num_boost_round=TOTALS_ROUNDS)
    assert MEGA <= seen if arm == "interpreted" else not seen, seen
    assert all(t.num_leaves == 15 for t in bst._gbdt.models)
    gap = _leaf_value_gap(bst, X, y, jnp.bfloat16 if arm == "interpreted"
                          else np.float32)
    assert gap < LEAF_VALUE_GAP_LIMIT, gap


# ---- categorical entries in the row-deciding kernels, entry by entry
def _cat_case(B, rng, F=8, n=9000, K=8):
    """Binned rows with bin 0 in every column, a wave table whose
    entries alternate numeric and categorical, and bitsets with bits set
    in their first and last word. 9,000 rows are 5 row blocks: the
    categorical megakernel sums 5 row partitions apart (wave_parts)."""
    from lightgbm_tpu.ops import histogram_pallas as hpk
    W = (B + 31) // 32
    X = rng.randint(0, B, size=(F, n)).astype(np.uint8)
    X[:, ::7] = 0
    table = np.full((16, 128), -1, np.int32)
    app, cand = [0, 3, 5, 7], list(range(K))
    for base, leaves in ((0, app), (7, cand)):
        m = len(leaves)
        table[base, :m] = leaves
        table[base + 1, :m] = rng.randint(0, F, size=m)
        table[base + 2, :m] = rng.randint(0, B - 1, size=m)
        table[base + 3, :m] = rng.randint(0, 2, size=m)
        table[base + 4, :m] = rng.choice([0, 1, 2], size=m)
        table[base + 5, :m] = rng.randint(0, B - 1, size=m)
        table[base + 6, :m] = B
    table[14, :K] = rng.randint(0, 2, size=K)
    table[15, :] = 12
    bits = rng.randint(0, 2 ** 32, size=(2, K, W), dtype=np.uint64) \
        .astype(np.uint32)
    bits[:, :, 0] |= 0x10                # a bit in the first word ...
    bits[:, :, W - 1] |= 1 << 3          # ... and one in the last
    bits[:, :, 0] &= ~np.uint32(1)       # bin 0 never goes left
    is_cat = np.zeros((2, K), bool)
    is_cat[:, 1::2] = True
    tbl, words = hpk.wave_cat_words(jnp.asarray(table),
                                    jnp.asarray(is_cat[0, :4]),
                                    jnp.asarray(bits[0, :4]),
                                    jnp.asarray(is_cat[1]),
                                    jnp.asarray(bits[1]))
    return X, table, is_cat, bits, tbl, words


def _select_chain(X, table, is_cat, bits, lor):
    """The portable route's rule (ops/grow_wave.py table_go_left), row by
    row: the applied entry relabels, then the candidate entry of the new
    leaf says which child's slot the row is in (-1: none)."""
    def go_left(row, base, j, cat, words):
        f, thr, dl = table[base + 1, j], table[base + 2, j], table[base + 3, j]
        mt, db, nb = table[base + 4, j], table[base + 5, j], table[base + 6, j]
        b = int(X[f, row])
        if cat:
            return bool((int(words[b >> 5]) >> (b & 31)) & 1)
        if (mt == 1 and b == db) or (mt == 2 and b == nb - 1):
            return bool(dl)
        return b <= thr

    new_lor, slot = lor.copy(), np.full(len(lor), -1, np.int32)
    for r in range(len(lor)):
        hit = np.flatnonzero(table[0, :4] == lor[r])
        if len(hit):
            j = hit[0]
            if not go_left(r, 0, j, is_cat[0, j], bits[0, j]):
                new_lor[r] = table[15, 0] + j
        hit = np.flatnonzero(table[7, :8] == new_lor[r])
        if len(hit):
            j = hit[0]
            if go_left(r, 7, j, is_cat[1, j], bits[1, j]) == bool(
                    table[14, j]):
                slot[r] = j
    return new_lor, slot


@pytest.mark.parametrize("kernel", ["megakernel", "relabel", "decide"])
@pytest.mark.parametrize("B", [64, 256])
def test_categorical_entries_match_the_select_chain(B, kernel):
    """``_wave_logic`` (through the megakernel and the relabel-only pass)
    and ``_wave_decide_kernel`` under the Pallas interpreter, with the
    bitset operand of ``wave_cat_words``, against the select-chain rule
    row by row: the new leaf of every row bit for bit, and for the
    megakernel the int8 histograms of the candidates' smaller children
    and their in-bag row counts."""
    from lightgbm_tpu.ops import histogram_pallas as hpk
    rng = np.random.RandomState(B)
    X, table, is_cat, bits, tbl, words = _cat_case(B, rng)
    n = X.shape[1]
    lor = rng.randint(0, 12, size=n).astype(np.int32)
    want_lor, want_slot = _select_chain(X, table, is_cat, bits, lor)
    if kernel == "decide":
        got_lor, got_slot = hpk.wave_decide_pallas(
            jnp.asarray(X), jnp.asarray(lor), tbl, interpret=True, cat=words)
        np.testing.assert_array_equal(np.asarray(got_slot), want_slot)
    elif kernel == "relabel":
        vals = jnp.zeros((2, n), jnp.int8)
        got_lor = hpk.wave_relabel_pallas(
            jnp.asarray(X.astype(np.int8)), vals, jnp.asarray(lor), tbl, B,
            interpret=True, cat=words)
    else:
        vals = rng.randint(-20, 20, size=(2, n)).astype(np.int8)
        in_bag = (rng.uniform(size=n) < 0.8).astype(np.float32)
        got_lor, hist, counts, _ = hpk.wave_pass_pallas(
            jnp.asarray(X.astype(np.int8)), jnp.asarray(vals),
            jnp.asarray(lor), tbl, 8, B, interpret=True,
            wide_lo=64 if B > 128 else 128, cat=words,
            in_bag=jnp.asarray(in_bag))
        want = np.zeros((8, 2, X.shape[0], B), np.int64)
        for r in np.flatnonzero(want_slot >= 0):
            for f in range(X.shape[0]):
                want[want_slot[r], :, f, X[f, r]] += vals[:, r]
        np.testing.assert_array_equal(np.asarray(hist), want)
        # each slot's in-bag rows: the smaller children's exact counts
        np.testing.assert_array_equal(np.asarray(counts), np.bincount(
            want_slot[(want_slot >= 0) & (in_bag > 0)], minlength=8))
    np.testing.assert_array_equal(np.asarray(got_lor), want_lor)


# ---- the wave pass compacts the rows that carry weight, then contracts
def _compact_case(kind, K, select, n, B, F, quantized, seed):
    """Binned rows, a wave table with K candidates, values and in-bag rows
    for one direct call of ``wave_pass_pallas``. ``select``: "some" (a
    random share of the rows lands in a candidate's smaller child, some
    of it moved there by an applied split), "none" or "all" (every row
    of the table in a candidate leaf, every one going to the smaller
    child). Categorical tables alternate numeric and bitset entries
    ("all" takes numeric entries only: bin 0 never goes left under a
    bitset)."""
    rng = np.random.RandomState(seed)
    X = rng.randint(0, B, size=(F, n)).astype(np.uint8)
    X[:, ::11] = 0
    table = np.full((16, 128), -1, np.int32)
    nl0 = 200
    app = [K + 1, K + 2, K + 3] if select == "some" else []
    cand = list(range(K))
    if select == "some":
        # the first candidates are the applied splits' new right children
        cand[:len(app)] = [nl0 + j for j in range(min(len(app), K))]
    for base, leaves in ((0, app), (7, cand)):
        m = len(leaves)
        if not m:
            continue
        table[base, :m] = leaves
        table[base + 1, :m] = rng.randint(0, F, size=m)
        table[base + 2, :m] = rng.randint(1, B - 2, size=m)
        table[base + 3, :m] = rng.randint(0, 2, size=m)
        table[base + 4, :m] = rng.choice([0, 1, 2], size=m)
        table[base + 5, :m] = rng.randint(0, B - 1, size=m)
        table[base + 6, :m] = B
    table[14, :K] = rng.randint(0, 2, size=K)
    table[15, :] = nl0
    if select == "all":
        table[9, :K] = B - 1            # every row goes left ...
        table[11, :K] = 0               # ... with no missing rule
        table[14, :K] = 1               # ... into the smaller child
        lor = rng.randint(0, K, size=n)
    elif select == "none":
        lor = rng.randint(K + 5, K + 40, size=n)
    else:
        lor = rng.randint(0, K + 6, size=n)
    W = (B + 31) // 32
    bits = rng.randint(0, 2 ** 32, size=(2, max(K, 4), W),
                       dtype=np.uint64).astype(np.uint32)
    bits[:, :, 0] &= ~np.uint32(1)      # bin 0 never goes left
    is_cat = np.zeros((2, max(K, 4)), bool)
    if kind == "cat" and select != "all":
        is_cat[:, 1::2] = True
    if quantized:
        vals = rng.randint(-127, 128, size=(2, n)).astype(np.int8)
    else:
        vals = rng.normal(size=(2, n)).astype(np.float32)
    in_bag = (rng.uniform(size=n) < 0.8).astype(np.float32)
    return X, table, is_cat, bits, lor.astype(np.int32), vals, in_bag


def _wave_reference(X, table, is_cat, bits, lor, K, cat):
    """new leaf of every row and the slot of its candidate's smaller
    child (-1: none), vectorised over rows (ops/grow_wave.py
    table_go_left's rule; on a categorical table a bitset entry tests
    its bin's bit and has no missing rule)."""
    def go_left(rows, base, j, side):
        f, thr, dl = table[base + 1, j], table[base + 2, j], table[base + 3, j]
        mt, db, nb = table[base + 4, j], table[base + 5, j], table[base + 6, j]
        b = X[f, rows].astype(np.int64)
        if cat and is_cat[side, j]:
            w = bits[side, j].astype(np.int64)
            return ((w[b >> 5] >> (b & 31)) & 1) == 1
        miss = ((mt == 1) & (b == db)) | ((mt == 2) & (b == nb - 1))
        return np.where(miss, dl == 1, b <= thr)

    new_lor, slot = lor.copy(), np.full(len(lor), -1, np.int32)
    for j in np.flatnonzero(table[0] >= 0):
        rows = np.flatnonzero(lor == table[0, j])
        new_lor[rows[~go_left(rows, 0, j, 0)]] = table[15, 0] + j
    for j in np.flatnonzero(table[7, :K] >= 0):
        rows = np.flatnonzero(new_lor == table[7, j])
        gl = go_left(rows, 7, j, 1)
        slot[rows[gl == bool(table[14, j])]] = j
    return new_lor, slot


# id -> (table kind, K, selection, rows, B, F, quantized). 9,000 rows are
# 5 row blocks with a tail (not a block multiple); "all" at 9,000 rows
# fills the staging buffer's tile four times over. A categorical table
# sums its rows in partitions (wave_parts): at 140,000 rows 32 partitions
# of 3 blocks, so "all" there flushes a full tile inside every partition
# and the rest at each partition's end.
COMPACT_CASES = {
    "numeric_f28_b64_k1": ("num", 1, "some", 9000, 64, 28, False),
    "numeric_f28_b64_k64": ("num", 64, "some", 9000, 64, 28, False),
    "numeric_f28_b64_k128": ("num", 128, "some", 9000, 64, 28, False),
    "numeric_quantized_k64": ("num", 64, "some", 9000, 64, 28, True),
    "numeric_hilo_b256_k8": ("num", 8, "some", 9000, 256, 9, True),
    "numeric_none_selected": ("num", 8, "none", 9000, 64, 28, False),
    "numeric_all_selected": ("num", 8, "all", 9000, 64, 28, True),
    "numeric_small_table": ("num", 4, "some", 700, 32, 6, False),
    "cat_f8_b248_k8": ("cat", 8, "some", 9000, 248, 8, False),
    "cat_f8_b248_k128_quantized": ("cat", 128, "some", 9000, 248, 8, True),
    "cat_none_selected": ("cat", 8, "none", 9000, 248, 8, True),
    "cat_all_selected_partitions": ("cat", 8, "all", 140000, 64, 8, True),
}


@pytest.mark.parametrize("case", list(COMPACT_CASES))
def test_compacted_wave_pass_matches_reference(case):
    """``wave_pass_pallas`` under the Pallas interpreter, direct, against
    a NumPy reference: every row's new leaf, the candidates' smaller
    children's histograms (exact on int8 values; float32 within 1e-6 of
    each bin's magnitude, the sum of its rows' |value| as bfloat16 holds
    them: the compacted tiles add the same rows in another grouping), on
    a categorical table each slot's in-bag rows, and the count of rows
    that reached the contraction, which is the selected rows'."""
    from lightgbm_tpu.ops import histogram_pallas as hpk
    kind, K, select, n, B, F, quantized = COMPACT_CASES[case]
    X, table, is_cat, bits, lor, vals, in_bag = _compact_case(
        kind, K, select, n, B, F, quantized, seed=len(case))
    cat = kind == "cat"
    want_lor, want_slot = _wave_reference(X, table, is_cat, bits, lor, K,
                                          cat)
    tbl, kw = jnp.asarray(table), {}
    if cat:
        tbl, words = hpk.wave_cat_words(
            tbl, jnp.asarray(is_cat[0, :4]), jnp.asarray(bits[0, :4]),
            jnp.asarray(is_cat[1, :K]), jnp.asarray(bits[1, :K]))
        kw = dict(cat=words, in_bag=jnp.asarray(in_bag))
    out = hpk.wave_pass_pallas(
        jnp.asarray(X.astype(np.int8)), jnp.asarray(vals), jnp.asarray(lor),
        tbl, K, B, interpret=True, wide_lo=64 if B > 128 else 128, **kw)
    np.testing.assert_array_equal(np.asarray(out[0]), want_lor)
    sel = np.flatnonzero(want_slot >= 0)
    assert {"none": 0, "all": n}.get(select, len(sel)) == len(sel)
    assert int(out[-1]) == len(sel)
    v = vals.astype(np.float64) if quantized else np.asarray(
        jnp.asarray(vals).astype(jnp.bfloat16).astype(jnp.float32),
        np.float64)
    want = np.zeros((K, 2, F, B))
    mag = np.zeros((K, 2, F, B))
    for f in range(F):
        for c in range(2):
            np.add.at(want, (want_slot[sel], c, f, X[f, sel]), v[c, sel])
            np.add.at(mag, (want_slot[sel], c, f, X[f, sel]),
                      np.abs(v[c, sel]))
    got = np.asarray(out[1], np.float64)
    if quantized:
        assert out[1].dtype == jnp.int32
        np.testing.assert_array_equal(got, want)
    else:
        assert np.all(np.abs(got - want) <= 1e-6 * mag), \
            np.max(np.abs(got - want) - 1e-6 * mag)
    if cat:
        np.testing.assert_array_equal(np.asarray(out[2]), np.bincount(
            want_slot[(want_slot >= 0) & (in_bag > 0)], minlength=K))
