"""Airline-on-time-shaped synthetic data, made on the host from ``--seed``.

The Data Expo 2009 table as szilard/benchm-ml and LightGBM's
``docs/Experiments.rst`` ("Expo") feed it — eight raw columns, in this
order: Month, DayofMonth, DayOfWeek, DepTime, UniqueCarrier, Origin,
Dest, Distance; the label "departure delayed 15 minutes or more" — with
the six columns that are NAMES kept as whole-number codes in float32
(``feature_types`` ``c c c q c c c q``) and the cardinalities the one-hot
width of 700 implies: 12, 31, 7, 22, 313, 313.

The rule (``airline_like``), block by block from child streams of one
``SeedSequence`` as ``datagen.higgs_like`` draws (the same seed gives the
same table on any number of threads):

* the calendar columns are near uniform (a mild seasonal tilt);
* carrier and both airports are Zipf-like over their levels, exponent
  1.1: the hubs carry most flights, and the 313-level columns overflow
  255 named bins by their RAREST levels only;
* DepTime is bimodal over 0-2359 (a morning and an evening bank),
  Distance log-normal around 700 miles;
* the label is Bernoulli of a logit that is a sum of SEEDED per-category
  effects — one table of effects a seed, shared by every stream of it —
  so that the best partition of a column is NOT an interval of its codes;
  plus a smooth term in DepTime (delays build up over the day), a small
  one in Distance, and one carrier x origin interaction; the intercept
  puts the positive share near 0.2.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Tuple

import numpy as np

#: rows per child stream; fixed, because it is part of what a seed means
BLOCK_ROWS = 500_000
_THREADS = 12

FEATURE_TYPES = ("c", "c", "c", "q", "c", "c", "c", "q")
#: levels of each categorical column (0: numeric), in column order
CARDINALITIES = (12, 31, 7, 0, 22, 313, 313, 0)
_ZIPF = {4: 1.1, 5: 1.1, 6: 1.1}        # carrier, origin, dest


def _effects(seed: int):
    """The seed's table of per-category effects and level weights: one
    draw, shared by the training and the held-out stream."""
    rng = np.random.default_rng(np.random.SeedSequence(
        entropy=int(seed), spawn_key=(1 << 20,)))
    scale = {0: 0.25, 1: 0.08, 2: 0.15, 4: 0.5, 5: 0.45, 6: 0.3}
    eff = {f: rng.normal(0.0, scale[f], c)
           for f, c in enumerate(CARDINALITIES) if c}
    weights = {}
    for f, c in enumerate(CARDINALITIES):
        if not c:
            continue
        if f in _ZIPF:
            # which CODE is the hub is the seed's: the order of the codes
            # says nothing about their counts
            w = 1.0 / np.arange(1, c + 1) ** _ZIPF[f]
            w = w[rng.permutation(c)]
        else:
            w = 1.0 + 0.15 * rng.random(c)
        weights[f] = np.cumsum(w / w.sum())
    pair = rng.normal(0.0, 0.6, (CARDINALITIES[4], 8))   # carrier x top hubs
    hubs = np.argsort(np.diff(weights[5], prepend=0.0))[-8:]
    return eff, weights, pair, hubs


def airline_like(rows: int, seed: int, stream: int = 0
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """``rows x 8`` float32 and their labels.  ``stream`` names an
    independent draw of the same seed (0 = training rows, 1 = held-out)."""
    eff, weights, pair, hubs = _effects(seed)
    hub_slot = np.full(CARDINALITIES[5], -1)
    hub_slot[hubs] = np.arange(len(hubs))
    X = np.empty((rows, 8), np.float32)
    y = np.empty(rows, np.float32)
    starts = list(range(0, rows, BLOCK_ROWS))
    children = np.random.SeedSequence(
        entropy=int(seed), spawn_key=(int(stream),)).spawn(len(starts))

    def fill(job):
        lo, child = job
        hi = min(lo + BLOCK_ROWS, rows)
        n = hi - lo
        rng = np.random.default_rng(child)
        logit = np.full(n, -1.75)
        codes = {}
        for f, c in enumerate(CARDINALITIES):
            if not c:
                continue
            code = np.minimum(np.searchsorted(
                weights[f], rng.random(n, dtype=np.float32)), c - 1)
            codes[f] = code
            X[lo:hi, f] = code
            logit += eff[f][code]
        # DepTime: two banks, hhmm; Distance: log-normal miles
        bank = rng.random(n, dtype=np.float32) < 0.45
        hour = np.where(bank, rng.normal(8.0, 1.8, n),
                        rng.normal(17.0, 2.6, n)) % 24.0
        minute = np.floor((hour % 1.0) * 60.0)
        X[lo:hi, 3] = np.floor(hour) * 100.0 + minute
        dist = np.exp(rng.normal(6.55, 0.75, n))
        X[lo:hi, 7] = np.floor(np.clip(dist, 30.0, 5000.0))
        logit += (0.9 * np.sin((hour - 11.0) * (np.pi / 24.0))
                  + 0.1 * np.log(dist / 700.0))
        slot = hub_slot[codes[5]]
        logit += np.where(slot >= 0, pair[codes[4], np.maximum(slot, 0)],
                          0.0)
        y[lo:hi] = rng.random(n) < 1.0 / (1.0 + np.exp(-logit))

    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(fill, zip(starts, children)))
    return X, y
