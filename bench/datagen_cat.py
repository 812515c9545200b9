"""Seeded synthetic flights in the shape of the ASA Data Expo 2009 airline
on-time table, as LightGBM's Expo benchmark reads it: eight columns, six
of them categorical, and a binary "delayed" label.

A cell's file gives the parameters under "data" ("kind": "airline") and
the kind calls ``make`` once for the training part and once for the
held-out part. Columns, in order (``CATEGORICAL`` names the categorical
ones by index):

  0 Month          12 categories, codes 0-11, uniform
  1 DayofMonth     31 categories, codes 0-30, weighted by the months
                   that have the day (29: 11.25 of 12, 30: 11, 31: 7)
  2 DayOfWeek      7 categories, codes 0-6, uniform
  3 DepTime        hhmm as a number (0-2359): the hour from a daily
                   profile, the minute uniform
  4 UniqueCarrier  22 categories, shares Zipf s = 1
  5 Origin         313 airports in the FAA hub tiers, equal shares
  6 Dest           within a tier: 30 large hubs 72 %, 31 medium 17 %,
                   72 small 8 %, 180 non-hubs 3 % (independent of Origin)
  7 Distance       miles between the two airports' seeded positions on a
                   2,500 x 1,200 mile plane, plus N(0, 20), at least 30,
                   whole miles

The *world* comes from the seed alone and is shared by both parts: which
code is which carrier or airport, the airports' positions, and the label's
effects. The label is a logistic draw around a score of per-category
effects of Carrier, Origin and Dest, an Origin x hour interaction, the
hour's own effect and small Month and DayOfWeek effects, cut at the
world's 81st percentile (about 19 % positives, the delayed-15-minutes
rate of the public airline tables).

Rows are drawn in fixed blocks, each from its own child of
``SeedSequence([seed, rows, part])``, so the same seed gives the same
bits whatever the number of threads.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Tuple

import numpy as np

BLOCK = 1 << 20
CATEGORICAL = (0, 1, 2, 4, 5, 6)
CARDINALITY = {0: 12, 1: 31, 2: 7, 4: 22, 5: 313, 6: 313}
TIERS = ((30, 0.72), (31, 0.17), (72, 0.08), (180, 0.03))
POSITIVE_SHARE = 0.19
# departures by hour, 0-23 (a daily profile: few at night, two peaks)
HOURS = np.array([2, 1, 1, 1, 1, 8, 30, 55, 60, 55, 50, 50, 52, 52, 50,
                  50, 55, 60, 58, 50, 40, 30, 15, 6], np.float64)


def _spec(spec: Dict) -> Dict:
    if spec.get("kind") != "airline":
        raise ValueError(f"unknown data kind {spec.get('kind')!r}")
    if int(spec.get("cols", 8)) != 8:
        raise ValueError("airline makes 8 columns")
    return spec


def airport_shares() -> np.ndarray:
    """[313] share of each airport rank (tiers in order, equal within)."""
    return np.concatenate([np.full(n, s / n) for n, s in TIERS])


class World:
    """What both parts share, from the seed alone."""

    def __init__(self, seed: int):
        g = np.random.Generator(np.random.PCG64(
            np.random.SeedSequence([int(seed), 8, 0x41495200])))
        n_air = CARDINALITY[5]
        # airport rank -> code, carrier rank -> code
        self.air_code = g.permutation(n_air)
        self.carrier_code = g.permutation(CARDINALITY[4])
        self.air_cdf = np.cumsum(airport_shares())
        zipf = 1.0 / np.arange(1, CARDINALITY[4] + 1)
        self.carrier_cdf = np.cumsum(zipf / zipf.sum())
        day_w = np.array([12.0] * 28 + [11.25, 11.0, 7.0])
        self.day_cdf = np.cumsum(day_w / day_w.sum())
        self.hour_cdf = np.cumsum(HOURS / HOURS.sum())
        for c in (self.air_cdf, self.carrier_cdf, self.day_cdf,
                  self.hour_cdf):
            c[-1] = 1.0
        # positions and effects by CODE
        self.pos = g.uniform([0.0, 0.0], [2500.0, 1200.0], size=(n_air, 2))
        self.e_carrier = g.normal(0.0, 0.35, CARDINALITY[4])
        self.e_origin = g.normal(0.0, 0.30, n_air)
        self.e_dest = g.normal(0.0, 0.25, n_air)
        self.e_oh = g.normal(0.0, 0.25, n_air)
        self.e_month = g.normal(0.0, 0.15, 12)
        self.e_dow = g.normal(0.0, 0.10, 7)
        # the hour's own effect: later departures are later
        self.e_hour = np.linspace(-0.8, 0.7, 24) + g.normal(0.0, 0.05, 24)
        self.threshold = 0.0
        # the label's cut: the 81st percentile of a fixed draw
        probe = np.random.Generator(np.random.PCG64(
            np.random.SeedSequence([int(seed), 8, 0x43555400])))
        _, z = self.draw(probe, 1 << 20)
        self.threshold = float(np.quantile(z, 1.0 - POSITIVE_SHARE))

    def draw(self, g: np.random.Generator, n: int
             ) -> Tuple[np.ndarray, np.ndarray]:
        """([n, 8] float32 rows, [n] float64 latent score + noise)."""
        X = np.empty((n, 8), np.float32)
        month = g.integers(0, 12, n)
        dow = g.integers(0, 7, n)
        hour = np.searchsorted(self.hour_cdf, g.random(n), side="right")
        carrier = self.carrier_code[
            np.searchsorted(self.carrier_cdf, g.random(n), side="right")]
        origin = self.air_code[
            np.searchsorted(self.air_cdf, g.random(n), side="right")]
        dest = self.air_code[
            np.searchsorted(self.air_cdf, g.random(n), side="right")]
        X[:, 0] = month
        X[:, 1] = np.searchsorted(self.day_cdf, g.random(n), side="right")
        X[:, 2] = dow
        X[:, 3] = hour * 100 + g.integers(0, 60, n)
        X[:, 4] = carrier
        X[:, 5] = origin
        X[:, 6] = dest
        d = np.hypot(*(self.pos[origin] - self.pos[dest]).T)
        X[:, 7] = np.maximum(np.rint(d + g.normal(0.0, 20.0, n)), 30.0)
        z = (self.e_carrier[carrier] + self.e_origin[origin]
             + self.e_dest[dest] + self.e_hour[hour]
             + self.e_oh[origin] * (hour - 12) / 6.0
             + self.e_month[month] + self.e_dow[dow]
             + g.logistic(0.0, 1.0, n))
        return X, z


def make(seed: int, spec: Dict, part: int = 0, threads: int = 8
         ) -> Tuple[np.ndarray, np.ndarray]:
    """``part`` 0: the training rows; 1: the held-out rows."""
    spec = _spec(spec)
    rows = int(spec["rows"])
    world = World(seed)
    n_blocks = -(-rows // BLOCK)
    children = np.random.SeedSequence(
        [int(seed), rows, int(part)]).spawn(n_blocks)
    X = np.empty((rows, 8), np.float32)
    y = np.empty(rows, np.float32)

    def fill(i: int) -> None:
        lo, hi = i * BLOCK, min((i + 1) * BLOCK, rows)
        g = np.random.Generator(np.random.PCG64(children[i]))
        X[lo:hi], z = world.draw(g, hi - lo)
        y[lo:hi] = z > world.threshold

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(fill, range(n_blocks)))
    return X, y
