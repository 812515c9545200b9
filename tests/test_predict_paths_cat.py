"""The held-out path walk with categorical nodes.

``predict_leaf_binned_paths`` (ops/predict.py: what the TPU runs for a
valid set's rows inside the batched scan) takes categorical nodes since
the deployment with categorical columns came in: a node's decision is
bit ``bin`` of its bitset, the word picked by select over the node's W
words, elementwise on the same [M, R] block as the numeric test. Here it
meets the lockstep gather walk (``predict_leaf_binned`` off the TPU),
which shares no code with it, leaf for leaf on seeded trees: mixed
numeric and categorical nodes, bitsets of 2 and 8 words with bits in the
first and the last, bin 0 in every column (never in a bitset), missing-
as-zero and missing-as-NaN numeric columns, row counts off the block.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.models.tree import MISSING_NAN, MISSING_ZERO
from lightgbm_tpu.ops import predict as pr
from lightgbm_tpu.ops.split import FeatureMeta


def _random_tree(rng, M, n_leaves):
    """Child arrays of a random ``n_leaves``-leaf tree padded to M nodes."""
    lc = np.zeros(M, np.int32)
    rc = np.zeros(M, np.int32)
    slot = [None]
    nl = 1
    for j in range(n_leaves - 1):
        leaf = int(rng.integers(nl))
        if slot[leaf] is not None:
            (lc if slot[leaf][1] else rc)[slot[leaf][0]] = j
        lc[j], rc[j] = ~leaf, ~nl
        slot[leaf] = (j, True)
        slot.append((j, False))
        nl += 1
    return lc, rc


@pytest.mark.parametrize("B,M,n_leaves,F,N,seed", [
    (64, 14, 15, 7, 1000, 1), (256, 254, 255, 8, 70000, 2),
    (256, 30, 17, 12, 33000, 3), (64, 254, 1, 5, 100, 4),
    (256, 254, 2, 8, 300, 5), (256, 62, 63, 3, 5000, 6)])
def test_path_walk_with_categorical_nodes_matches_the_gather_walk(
        B, M, n_leaves, F, N, seed):
    rng = np.random.default_rng(seed)
    W = B // 32
    lc, rc = _random_tree(rng, M, n_leaves)
    is_cat_f = rng.random(F) < 0.5
    is_cat_f[0] = True
    feat = rng.integers(0, F, size=M).astype(np.int32)
    feat[:M // 2] = 0                    # categorical nodes near the root
    bits = rng.integers(0, 2 ** 32, size=(M, W), dtype=np.uint64) \
        .astype(np.uint32)
    bits[:, 0] |= 0x20
    bits[:, W - 1] |= 1 << 30
    bits[:, 0] &= ~np.uint32(1)          # bin 0: never left
    meta = FeatureMeta(
        jnp.full((F,), B, jnp.int32),
        jnp.asarray(np.where(is_cat_f, 0, rng.choice(
            [0, MISSING_ZERO, MISSING_NAN], size=F)).astype(np.int32)),
        jnp.asarray(rng.integers(0, B, size=F).astype(np.int32)),
        jnp.asarray(is_cat_f))
    X = rng.integers(0, B, size=(F, N)).astype(np.uint8)
    X[:, ::5] = 0
    args = [jnp.asarray(a) for a in (
        feat, rng.integers(0, B - 2, size=M).astype(np.int32),
        rng.random(M) < 0.5, lc, rc)] + [
        jnp.int32(n_leaves), jnp.asarray(X), meta,
        jnp.asarray(is_cat_f[feat]), jnp.asarray(bits)]
    assert pr.path_walk_applies(args[6], args[8])
    want = np.asarray(jax.jit(pr.predict_leaf_binned)(*args))
    got = np.asarray(jax.jit(pr.predict_leaf_binned_paths)(*args))
    np.testing.assert_array_equal(got, want)
    assert want.min() >= 0 and want.max() < max(n_leaves, 1)
    if n_leaves > 2:                     # the categorical nodes decided
        assert len(np.unique(want)) > 2
