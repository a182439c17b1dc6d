"""Bosch-shaped synthetic data, made on the host from ``--seed``: a wide
table that is mostly holes, where a hole means something.

Kaggle's "Bosch Production Line Performance" (``train_numeric.csv``:
1,183,747 parts x 968 measurements, 81% of the cells empty, 0.58% of the
parts failed) is not here, so its SHAPE is drawn: the columns are the
measurements of 52 stations, consecutive columns to a station; a part
visits a station or it does not, so a row has a whole station's columns
or none of them; each station has its own share of the parts, drawn from
the seed and scaled so that ``present_share`` (19%) of all cells hold a
value.  Values are gaussians.  Every column is finite in at least one
row (row ``s`` of the table visits station ``s``).

The label is a rare event of a rule that reads values AND absences: a
part fails where

    [A absent] * 2.0  +  [A present] * 0.7 * a
      +  [B present] * 0.5 * b  +  [C present] * 0.3 * c1 * c2
      +  0.6 * noise

passes the threshold that leaves ``positive_share`` (0.58%) of the parts
above it (``a``, ``b``, ``c1``, ``c2``: the first columns of three
stations the seed picks; nine parts in ten visit A, under half B and C).
So the tenth of the parts that skipped station A holds six failures in
seven, and a part that measured high there is suspect too: the split
that isolates them sends the rows WITHOUT ``a`` to the right, with the
high values (a gain of a split is ``p_l^2/n_l + p_r^2/n_r - p^2/n`` in
failures ``p`` and rows ``n``: it pays to put the failures in the small
child), and a fit whose default direction is fixed to the left cannot
make it — the control of ``benchmark/tests/test_missing.py``.

The drawing is ``datagen.higgs_like``'s: rows block by block from child
streams of one ``SeedSequence``, the blocks filled by a few threads
straight into ``float32``, so the same seed gives the same table on any
number of threads.  The stations, their shares, the rule's columns and
its threshold are drawn from the seed alone, not from ``stream``: the
held-out rows (``stream=1``) are more parts of the same line.
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import Tuple

import numpy as np

#: rows per child stream; fixed, because it is part of what a seed means
BLOCK_ROWS = 65_536
_THREADS = 8
STATIONS = 52
PRESENT_SHARE = 0.19
POSITIVE_SHARE = 0.0058
#: rows of the draw that places the label's threshold
_CALIBRATION_ROWS = 1 << 20
#: the spawn key of the line's own stream (no block has it)
_LINE_KEY = (1 << 20,)


@dataclasses.dataclass(frozen=True)
class Line:
    """What a seed says of the production line, whatever the stream."""
    bounds: np.ndarray         # [S + 1] first column of each station
    share: np.ndarray          # [S] share of the parts that visit it
    a: int                     # the rule's columns ...
    b: int
    c1: int
    c2: int
    station_a: int             # ... and their stations
    station_b: int
    station_c: int
    threshold: float


def _score(pa, pb, pc, a, b, c1, c2, noise):
    return (np.where(pa, 0.7 * a, 2.0) + np.where(pb, 0.5 * b, 0.0)
            + np.where(pc, 0.3 * c1 * c2, 0.0) + 0.6 * noise)


def line_of(features: int, seed: int) -> Line:
    if features < 8:
        raise ValueError("the label rule reads four columns of three "
                         "stations")
    rng = np.random.default_rng(np.random.SeedSequence(
        entropy=int(seed), spawn_key=_LINE_KEY))
    n_st = min(STATIONS, features // 2)
    bounds = np.linspace(0, features, n_st + 1).round().astype(np.int64)
    width = np.diff(bounds)
    # the rule's stations: most parts visit A, so those that skip it are
    # a small group; B and C carry values and absences in like numbers
    sa, sb, sc = (int(s) for s in rng.choice(n_st, size=3, replace=False))
    rest = np.ones(n_st, bool)
    rest[[sa, sb, sc]] = False
    # the others' shares spread like a line's — a few stations nearly
    # every part visits, many that few do — scaled to the table's share
    share = rng.beta(0.7, 2.0, n_st)
    share[[sa, sb, sc]] = (0.9, 0.45, 0.4)
    fixed = float(share[~rest] @ width[~rest])
    for _ in range(50):
        share[rest] = np.clip(
            share[rest] * (PRESENT_SHARE * features - fixed)
            / float(share[rest] @ width[rest]), 0.02, 0.98)
    a, b, c1 = (int(bounds[s]) for s in (sa, sb, sc))
    c2 = c1 + 1                          # every station has two columns
    # the threshold, from a draw of the score alone
    m = _CALIBRATION_ROWS
    pres = rng.random((3, m)) < share[[sa, sb, sc]][:, None]
    z = rng.standard_normal((5, m))
    score = _score(pres[0], pres[1], pres[2], z[0], z[1], z[2], z[3], z[4])
    return Line(bounds, share, a, b, c1, c2, sa, sb, sc,
                float(np.quantile(score, 1.0 - POSITIVE_SHARE)))


def label_rule(X: np.ndarray, noise: np.ndarray, line: Line) -> np.ndarray:
    a, b = X[:, line.a], X[:, line.b]
    c1, c2 = X[:, line.c1], X[:, line.c2]
    pa, pb, pc = ~np.isnan(a), ~np.isnan(b), ~np.isnan(c1)
    score = _score(pa, pb, pc, np.nan_to_num(a), np.nan_to_num(b),
                   np.nan_to_num(c1), np.nan_to_num(c2), noise)
    return (score > line.threshold).astype(np.float32)


def bosch_like(rows: int, features: int, seed: int,
               stream: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """``rows x features`` float32 with NaN for every cell of a station a
    row did not visit, and the rows' labels.  ``stream`` names an
    independent draw of the same line (0 = training rows, 1 = held-out)."""
    line = line_of(features, seed)
    n_st = len(line.share)
    width = np.diff(line.bounds)
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)
    starts = list(range(0, rows, BLOCK_ROWS))
    children = np.random.SeedSequence(
        entropy=int(seed), spawn_key=(int(stream),)).spawn(len(starts))

    def fill(job):
        lo, child = job
        hi = min(lo + BLOCK_ROWS, rows)
        rng = np.random.default_rng(child)
        visits = rng.random((hi - lo, n_st), dtype=np.float32) < \
            line.share.astype(np.float32)[None, :]
        # row s of the table visits station s: no column is empty
        for s in range(max(lo, 0), min(hi, n_st)):
            visits[s - lo, s] = True
        rng.standard_normal(out=X[lo:hi], dtype=np.float32)
        np.copyto(X[lo:hi], np.nan,
                  where=~np.repeat(visits, width, axis=1))
        noise = rng.standard_normal(hi - lo, dtype=np.float32)
        y[lo:hi] = label_rule(X[lo:hi], noise, line)

    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(fill, zip(starts, children)))
    return X, y
