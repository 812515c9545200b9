"""A run with the timed path broken underneath has to come out not
correct, once for each fault a one-chip training cell can have. The
harness's look for a chip is skipped; the rest of a run is driven as it
is on the chip, and judged by the cell's own limits."""

import numpy as np
import pytest

import run as harness
from conftest import TOY

CELL = "higgs28-b63.train"


class Sound:
    pass


class StateUnchanged:
    """A step that returns its state unchanged: trees are grown, the
    scores stay where they were."""

    def after_chunk(self, booster, before):
        booster._gbdt.scores = before


class HalfTheBatch:
    """Half of the rows left out of every sum, the mean taken over the
    rest: the in-bag mask the scan takes is zero on the second half."""

    def after_init(self, booster):
        import jax.numpy as jnp
        g = booster._gbdt
        n = g._host_pad
        half = jnp.concatenate(
            [jnp.ones((n // 2,), jnp.float32),
             jnp.zeros((n - n // 2,), jnp.float32)])
        # whichever of the two the batched scan takes (gbdt.py: the
        # sampling mode decides)
        g._in_bag_ones = g._in_bag_dev = half


class CoarseBins:
    """Ingest at the next narrower kernel width: the program is handed
    ``max_bin`` 31 where the configuration states 63, so every bin holds
    twice its share of the rows. The trees are sound trees of those
    bins; only the edges' own numbers see it."""

    def params(self, params):
        return dict(params, max_bin=31)


class LeafAltered:
    """An answer altered where it is produced: one leaf output of the
    second tree moved by a hundredth of itself."""

    def trees(self, trees):
        node = trees[1]["tree_structure"]
        while "left_child" in node:
            node = node["left_child"]
        node["leaf_value"] *= 1.01
        return trees


def _run(tamper):
    return harness.run_cell(CELL, 77, 0.1, False, require_chip=False,
                            tamper=tamper, overrides=TOY)


def test_sound_run_is_correct():
    res = _run(Sound())
    assert res["correct"], res["compared"]


@pytest.mark.parametrize("fault", [StateUnchanged, HalfTheBatch,
                                   CoarseBins, LeafAltered])
def test_fault_reads_not_correct(fault):
    res = _run(fault())
    over = [k for k, (v, lim) in res["compared"].items() if v > lim]
    assert not res["correct"] and over, res["compared"]
