"""What ``compare.edge_numbers`` holds ingest's bin edges to, on edges
made by hand: the reference bins with the program's edges, so the edges
themselves are judged by what the configuration states (at most
``max_bin`` bins a column, bounds that rise to +inf, no bin much heavier
than its even share of the raw rows)."""

import numpy as np
import pytest

import compare

ROWS, MAX_BIN = 64000, 4
EVEN = [-0.6745, 0.0, 0.6745, np.inf]        # quartiles of N(0, 1)


def _numbers(edges):
    x = np.random.default_rng(7).standard_normal(ROWS).astype(np.float32)
    count = np.zeros((1, 8), np.int64)
    e = np.sort(np.asarray(edges[:-1], np.float32))
    count[0] = np.bincount(np.searchsorted(e, x, side="left"),
                           minlength=8)[:8]
    return compare.edge_numbers([np.asarray(edges)], count, ROWS, MAX_BIN)


def test_even_edges_read_sound():
    n = _numbers(EVEN)
    assert n["bin_edges_bad"] == 0 and abs(n["bin_mass_gap"]) < 0.03


@pytest.mark.parametrize("edges,bad,gap_over", [
    ([0.0, np.inf], 0, 0.9),                      # half as many bins
    ([-3.0, -2.5, -2.0, np.inf], 0, 2.5),         # all mass in the last
    ([-0.6745, 0.6745, 0.0, np.inf], 1, -1.0),    # a bound that falls
    ([-1.0, -0.5, 0.0, 0.5, np.inf], 1, -1.0),    # five bins for four
    ([-0.6745, 0.0, 0.6745, 9.0], 1, -1.0),       # no +inf at the end
])
def test_broken_edges_read_over(edges, bad, gap_over):
    n = _numbers(edges)
    assert n["bin_edges_bad"] == bad and n["bin_mass_gap"] > gap_over
