"""Covertype-shaped synthetic rows, made on the host from ``--seed``.

UCI Covertype (Blackard & Dean 1999) has 54 integer columns — ten
quantitative ones (elevation, aspect, slope, three distances to water and
roads, three hillshades 0-254, the distance to fire points), four
wilderness-area indicators and forty soil-type indicators, one of each
set a row — and seven cover types at very unequal shares.  The files are
not here, so :func:`covtype_like` draws rows of that SHAPE: every value a
whole number (ties everywhere: a hillshade has at most 255 values, an
indicator two, so cut points repeat and a value EQUAL to a cut sits in
every row), one wilderness area and one soil type a row, and labels 0-6
at the data set's shares.

The rule reads three things, as the forest does: the ELEVATION BAND (each
cover type has its own, overlapping its neighbours'), the WILDERNESS AREA
and the SOIL GROUP (eight groups of five soil types) — a row's label is
drawn at the data set's shares and its elevation, area and soil group
from that label's own distributions, which is the same joint law as a
noisy rule from the three to the label and gives the shares exactly.  The
distance to roads leans on the label a little; the other seven
quantitative columns carry none of it (the hillshades are functions of
aspect and slope, so they are correlated columns of few values, which the
splits must reject).

Rows are drawn block by block from child streams of one ``SeedSequence``
by a few threads, as ``datagen.higgs_like`` draws its own: the same seed
gives the same matrix on any number of threads.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Tuple

import numpy as np

#: rows per child stream; fixed, because it is part of what a seed means
BLOCK_ROWS = 500_000
_THREADS = 8

FEATURES = 54
CLASSES = 7
#: the data set's rows of each cover type (of 581,012)
CLASS_ROWS = (211_840, 283_301, 35_754, 2_747, 9_493, 17_367, 20_510)
CLASS_SHARES = tuple(c / 581_012 for c in CLASS_ROWS)

#: the columns: ten quantitative, then one-of-4 and one-of-40
ELEVATION, ASPECT, SLOPE, H_HYDRO, V_HYDRO, H_ROAD = 0, 1, 2, 3, 4, 5
SHADE_9AM, SHADE_NOON, SHADE_3PM, H_FIRE = 6, 7, 8, 9
WILDERNESS = slice(10, 14)
SOIL = slice(14, 54)
SOIL_GROUPS = 8

# a cover type's elevation band (metres): mean and spread, by the data
# set's own order — spruce/fir, lodgepole pine, ponderosa pine,
# cottonwood/willow, aspen, douglas-fir, krummholz
_ELEV_MEAN = np.array([3128., 2921., 2395., 2224., 2787., 2419., 3361.])
_ELEV_SD = np.array([160., 190., 190., 100., 95., 170., 105.])
# P(wilderness area | cover type): Rawah, Neota, Comanche Peak, Cache la
# Poudre — the low-elevation types grow in the last two
_AREA = np.array([[.50, .09, .41, .00],
                  [.52, .03, .44, .01],
                  [.00, .00, .40, .60],
                  [.00, .00, .00, 1.0],
                  [.40, .00, .60, .00],
                  [.00, .00, .44, .56],
                  [.25, .11, .64, .00]])
# P(soil group | cover type): eight groups of five soil types, from the
# dry low ones to the rocky high ones
_GROUP = np.array([[.00, .01, .03, .08, .30, .33, .15, .10],
                   [.01, .03, .08, .15, .30, .28, .12, .03],
                   [.40, .35, .15, .07, .03, .00, .00, .00],
                   [.60, .25, .10, .05, .00, .00, .00, .00],
                   [.00, .05, .15, .20, .40, .15, .05, .00],
                   [.30, .35, .20, .10, .05, .00, .00, .00],
                   [.00, .00, .00, .01, .04, .15, .35, .45]])
# P(soil type | its group): the same skew in every group
_IN_GROUP = np.array([.40, .25, .15, .12, .08])


def _pick(rng, table: np.ndarray, label: np.ndarray) -> np.ndarray:
    """One draw a row from ``table[label]`` (rows of probabilities)."""
    cdf = np.cumsum(table, axis=1)
    cdf[:, -1] = 1.0
    u = rng.random(len(label))
    return (u[:, None] >= cdf[label]).sum(axis=1)


def _whole(a, lo, hi):
    return np.clip(np.rint(a), lo, hi)


def _fill(X: np.ndarray, y: np.ndarray, rng) -> None:
    n = len(y)
    label = _pick(rng, np.asarray(CLASS_SHARES)[None, :],
                  np.zeros(n, np.int64))
    y[:] = label
    X[:] = 0.0
    X[:, ELEVATION] = _whole(rng.normal(_ELEV_MEAN[label], _ELEV_SD[label]),
                             1859, 3858)
    aspect = rng.integers(0, 361, n)
    slope = _whole(rng.gamma(3.5, 4.0, n), 0, 66)
    X[:, ASPECT] = aspect
    X[:, SLOPE] = slope
    X[:, H_HYDRO] = _whole(rng.gamma(1.6, 170.0, n), 0, 1397)
    X[:, V_HYDRO] = _whole(rng.normal(46.0, 58.0, n), -173, 601)
    # roads are nearer in the low country: a lean on the label, no more
    X[:, H_ROAD] = _whole(rng.gamma(2.2, 1070.0, n)
                          * (0.55 + 0.45 * (_ELEV_MEAN[label] - 2224.)
                             / (3361. - 2224.)), 0, 7117)
    # hillshade index 0-254 of the sun at 9am, noon and 3pm on a slope of
    # that aspect, plus a little noise: few values, tied, correlated
    rad, tilt = np.deg2rad(aspect), np.deg2rad(slope)
    for col, (azimuth, altitude) in ((SHADE_9AM, (110., 40.)),
                                     (SHADE_NOON, (180., 62.)),
                                     (SHADE_3PM, (250., 40.))):
        az, alt = np.deg2rad(azimuth), np.deg2rad(altitude)
        shade = (np.sin(alt) * np.cos(tilt)
                 + np.cos(alt) * np.sin(tilt) * np.cos(az - rad))
        X[:, col] = _whole(254.0 * np.maximum(shade, 0.0)
                           + rng.normal(0.0, 4.0, n), 0, 254)
    X[:, H_FIRE] = _whole(rng.gamma(2.3, 860.0, n), 0, 7173)
    rows = np.arange(n)
    X[rows, WILDERNESS.start + _pick(rng, _AREA, label)] = 1.0
    group = _pick(rng, _GROUP, label)
    soil = 5 * group + _pick(rng, _IN_GROUP[None, :], np.zeros(n, np.int64))
    X[rows, SOIL.start + soil] = 1.0


def covtype_like(rows: int, seed: int, stream: int = 0,
                 features: int = FEATURES) -> Tuple[np.ndarray, np.ndarray]:
    """``rows x 54`` float32 of whole numbers and their labels 0-6
    (float32).  ``stream`` names an independent draw of the same seed
    (0 = training rows, 1 = held-out)."""
    if features != FEATURES:
        raise ValueError(f"Covertype has {FEATURES} columns, not {features}")
    X = np.empty((rows, FEATURES), np.float32)
    y = np.empty(rows, np.float32)
    starts = list(range(0, rows, BLOCK_ROWS))
    children = np.random.SeedSequence(
        entropy=int(seed), spawn_key=(int(stream),)).spawn(len(starts))

    def fill(job):
        lo, child = job
        hi = min(lo + BLOCK_ROWS, rows)
        _fill(X[lo:hi], y[lo:hi], np.random.default_rng(child))

    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(fill, zip(starts, children)))
    return X, y
