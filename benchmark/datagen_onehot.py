"""Allstate-shaped synthetic rows, made on the host from ``--seed`` as CSR
blocks: a one-hot table that is 99.3% absent entries and never exists
dense.

Kaggle's "Allstate Claim Prediction Challenge" table as the XGBoost paper
runs it (Chen & Guestrin 2016, Table 2: 10M x 4,227, "insurance claim
classification") is not here, so its SHAPE is drawn: twelve continuous
columns present in every row (the table's ``Var1-8`` and ``NVVar1-4``),
then the indicator columns of nineteen categorical FIELDS, one-hot — the
vehicle's make, model and submodel, its model year, the calendar year,
``Cat1-12``, ``OrdCat`` and ``NVCat`` — whose level counts (:data:`FIELDS`)
sum to 4,215, two of them past 1,000.  A row sets ONE indicator a field,
or none where the cell is empty (:data:`ABSENT_SHARE` of the cells), so
about 30.4 of a row's 4,227 entries are there.  Levels are Zipf-spread
within a field (:data:`ZIPF`): a submodel's rank-r level holds a share
proportional to ``r ** -1.4``, so of 1,250,000 rows the commonest holds a
third and some 680 of the rarest fewer than ten each (three of them one
row, one none, at seed 12345) — the levels a sketch must not lose.

The label is a rare event (:data:`POSITIVE_SHARE`, the challenge's share
of policies with a claim) of a noisy rule that reads three continuous
columns and the levels of four fields (make, model year, ``Cat1``,
``NVCat``: each level has an effect drawn from the seed), thresholded
where a calibration draw says that share lies above.

Rows come block by block (:data:`BLOCK_ROWS`) from child streams of one
``SeedSequence``, so the same seed gives the same rows however many
blocks a caller asks for at a time; the fields' effects and the threshold
are drawn from the seed alone, not from ``stream``: the held-out rows
(``stream=1``) are more policies of the same book.  Nothing here is
dense: :func:`allstate_like` yields ``(offset, index, value, y)`` a block.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Sequence, Tuple

import numpy as np

#: rows per child stream; fixed, because it is part of what a seed means
BLOCK_ROWS = 65_536
NUMERIC = 12
#: (field, levels) in column order after the numeric columns
FIELDS = (("make", 75), ("model", 1302), ("submodel", 2739),
          ("model_year", 29), ("calendar_year", 3),
          ("cat1", 10), ("cat2", 3), ("cat3", 6), ("cat4", 3), ("cat5", 3),
          ("cat6", 5), ("cat7", 4), ("cat8", 3), ("cat9", 2), ("cat10", 3),
          ("cat11", 6), ("cat12", 6), ("ordcat", 7), ("nvcat", 6))
LEVELS = tuple(n for _f, n in FIELDS)
FEATURES = NUMERIC + sum(LEVELS)                   # 4,227
ZIPF = 1.4
ABSENT_SHARE = 0.03
POSITIVE_SHARE = 0.0073
#: the fields the label reads, by position in :data:`FIELDS`
_RULE_FIELDS = (0, 3, 5, 18)
_CALIBRATION_ROWS = 1 << 20
#: the spawn key of the book's own stream (no block has it)
_BOOK_KEY = (1 << 20,)


@dataclasses.dataclass(frozen=True)
class Book:
    """What a seed says of the book of policies, whatever the stream."""
    cdf: Tuple[np.ndarray, ...]       # per field, the levels' cumulated shares
    first: np.ndarray                 # per field, its first column
    effect: Tuple[np.ndarray, ...]    # per rule field, each level's effect
    threshold: float


def _score(num: np.ndarray, effects: np.ndarray, noise: np.ndarray
           ) -> np.ndarray:
    return (0.9 * num[:, 0] - 0.7 * num[:, 3] + 0.5 * num[:, 1] * num[:, 7]
            + effects + 0.8 * noise)


def field_bounds(levels: Sequence[int] = LEVELS) -> np.ndarray:
    """First column of each field, and one past the last."""
    return NUMERIC + np.concatenate([[0], np.cumsum(levels)]).astype(np.int64)


def levels_of(config: dict) -> Tuple[int, ...]:
    """The fields' level counts a configuration runs: the table's own
    unless the file gives ``field_levels`` (the self-tests' toy table),
    and either way they make up the file's ``features``."""
    levels = tuple(int(n) for n in config.get("field_levels", LEVELS))
    if NUMERIC + sum(levels) != int(config["features"]):
        raise ValueError(f"{NUMERIC} numeric columns and {sum(levels)} "
                         f"levels are not {config['features']} features")
    return levels


def _draw(rng: np.random.Generator, book: Book, m: int):
    """``m`` rows: numeric values ``[m, 12]`` float32, each field's level
    ``[m, fields]`` (-1 where the cell is empty) and the label noise."""
    num = rng.standard_normal((m, NUMERIC), dtype=np.float32)
    u = rng.random((m, len(book.cdf)))
    level = np.empty((m, len(book.cdf)), np.int64)
    for f, cdf in enumerate(book.cdf):
        level[:, f] = np.minimum(np.searchsorted(cdf, u[:, f], side="right"),
                                 len(cdf) - 1)
    level[rng.random((m, len(book.cdf))) < ABSENT_SHARE] = -1
    noise = rng.standard_normal(m)
    return num, level, noise


def _effects(book: Book, level: np.ndarray) -> np.ndarray:
    out = np.zeros(len(level))
    for eff, f in zip(book.effect, _RULE_FIELDS):
        lv = level[:, f]
        out += np.where(lv >= 0, eff[np.maximum(lv, 0)], 0.0)
    return out


def book_of(seed: int, levels: Sequence[int] = LEVELS) -> Book:
    rng = np.random.default_rng(np.random.SeedSequence(
        entropy=int(seed), spawn_key=_BOOK_KEY))
    cdf = []
    for n in levels:
        # which level is common is the seed's: ranks dealt to the levels
        share = (1.0 + rng.permutation(int(n))) ** -ZIPF
        cdf.append(np.cumsum(share / share.sum()))
    effect = tuple(rng.normal(0.0, 0.8, int(levels[f])) for f in _RULE_FIELDS)
    book = Book(tuple(cdf), field_bounds(levels)[:-1], effect, 0.0)
    num, level, noise = _draw(rng, book, _CALIBRATION_ROWS)
    score = _score(num.astype(np.float64), _effects(book, level), noise)
    return dataclasses.replace(book, threshold=float(
        np.quantile(score, 1.0 - POSITIVE_SHARE)))


def _block(rng: np.random.Generator, book: Book, m: int):
    num, level, noise = _draw(rng, book, m)
    y = (_score(num.astype(np.float64), _effects(book, level), noise)
         > book.threshold).astype(np.float32)
    # a row's entries in column order: the numerics, then each field's
    # set indicator
    cols = np.empty((m, NUMERIC + len(book.cdf)), np.int64)
    cols[:, :NUMERIC] = np.arange(NUMERIC)
    cols[:, NUMERIC:] = book.first + level
    vals = np.ones(cols.shape, np.float32)
    vals[:, :NUMERIC] = num
    there = np.ones(cols.shape, bool)
    there[:, NUMERIC:] = level >= 0
    offset = np.concatenate([[0], np.cumsum(there.sum(axis=1))])
    return offset.astype(np.int64), cols[there], vals[there], y


def allstate_like(rows: int, seed: int, stream: int = 0,
                  levels: Sequence[int] = LEVELS
                  ) -> Iterator[Tuple[np.ndarray, np.ndarray, np.ndarray,
                                      np.ndarray]]:
    """``rows`` rows as CSR blocks of at most :data:`BLOCK_ROWS`:
    ``(offset int64[m + 1], index int64[nnz], value float32[nnz], y
    float32[m])``, a row's indices ascending.  ``stream`` names an
    independent draw of the same book (0 = training rows, 1 = held-out);
    ``levels`` the fields' level counts (the table's own: 4,227 columns)."""
    book = book_of(seed, levels)
    starts = range(0, int(rows), BLOCK_ROWS)
    children = np.random.SeedSequence(
        entropy=int(seed), spawn_key=(int(stream),)).spawn(len(starts))
    for lo, child in zip(starts, children):
        yield _block(np.random.default_rng(child), book,
                     min(BLOCK_ROWS, int(rows) - lo))
