"""MSLR-shaped synthetic data, made on the host from ``--seed``: documents
in query groups of very different sizes, graded 0-4, handed over with the
queries NOT in order.

MSLR-WEB30K (Qin and Liu 2013: 31,531 queries, 3,771,125 query-url pairs
x 136 features, relevance 0-4, 1 to 1,251 documents a query) is not
here, so its SHAPE is drawn.

*Group sizes* (:func:`group_sizes`): a lognormal law (sigma 0.8) scaled
to the mean the configuration states, rounded, held inside ``[1,
max_group]``; one query is given exactly ``max_group`` documents and one
exactly 1, and single documents are moved between the others until the
sizes sum to exactly ``rows``.  The sizes are the DATA SET's, as its rows
and columns are: they are drawn from the law's own fixed seed and the
stream, NOT from ``--seed`` — MSLR-WEB30K has one list of query sizes
whatever is trained on it, and the sizes shape the program (how many
queries each width bucket holds), so a run with another seed runs the
same program on other documents.  Held-out queries (``stream=1``) are
other queries of the same law.

*Row order*: the system does the grouping, so it is not done here.
Whole queries are shuffled, and one query in eight is riffled with its
neighbour, document by document — a query's documents are then neither
adjacent nor first seen in id order.  Inside a query the documents keep
one order (the order they appear in), which is what "position in the
query" means for the rule for ties.

*Relevance*: a latent score of a few columns with an interaction,

    0.9 * x0 + 0.7 * x1 * x2 + 0.5 * x3 - 0.4 * |x4| + u_q + 0.7 * noise

cut at the four thresholds that leave the shares 51.5 / 32.5 / 13.4 /
1.9 / 0.8% of MSLR's five grades (thresholds from a calibration draw of
the seed alone).  ``u_q`` is a per-QUERY offset (sigma 0.8) added to the
latent score AND to columns 0 and 5: a query with a high offset holds
more relevant documents, and a model that ranks across queries instead
of inside them (a pointwise fit, a gradient without groups) spends its
splits on column 5, which orders no pair of one query.

The drawing is ``datagen.higgs_like``'s: rows block by block from child
streams of one ``SeedSequence``, the blocks filled by a few threads
straight into ``float32``, so the same seed gives the same table on any
number of threads.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Tuple

import numpy as np

#: rows per child stream; fixed, because it is part of what a seed means
BLOCK_ROWS = 65_536
_THREADS = 8
#: MSLR-WEB30K's shares of the grades 0..4
GRADE_SHARES = (0.515, 0.325, 0.134, 0.019, 0.008)
#: one query in this many is riffled with its neighbour
INTERLEAVE_EVERY = 8
_SIGMA_SIZES = 0.8
#: the seed of the law of the sizes: part of the data set's shape
_SIZES_SEED = 1306_2597
_SIGMA_OFFSET = 0.8
_CALIBRATION_ROWS = 1 << 20
#: spawn keys of the streams that are not row blocks
_SIZES_KEY, _ORDER_KEY, _OFFSET_KEY, _RULE_KEY = (
    (1 << 20,), (1 << 20) + 1, (1 << 20) + 2, ((1 << 20) + 3,))


def _rng(seed: int, *key: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        entropy=int(seed), spawn_key=tuple(int(k) for k in key)))


def group_sizes(queries: int, rows: Optional[int], max_group: int,
                stream: int = 0) -> np.ndarray:
    """Documents of each of ``queries`` queries: heavy-tailed, smallest 1,
    largest exactly ``max_group``, summing to exactly ``rows`` (``None``:
    whatever the law gives at a mean of a tenth of ``max_group``).  The
    same list for every ``--seed``."""
    rng = _rng(_SIZES_SEED, *_SIZES_KEY, stream)
    mean = (rows / queries) if rows is not None else max_group / 10.0
    raw = rng.lognormal(0.0, _SIGMA_SIZES, queries)
    lens = np.clip(np.round(raw * (mean / raw.mean())), 1,
                   max_group).astype(np.int64)
    pinned = np.zeros(queries, bool)
    if queries >= 2:
        big, small = rng.choice(queries, size=2, replace=False)
        lens[big], lens[small] = max_group, 1
        pinned[[big, small]] = True
    if rows is None:
        return lens
    if not queries <= rows <= (queries - 2) * max_group + max_group + 1:
        raise ValueError("rows do not fit the queries")
    diff = int(rows - lens.sum())
    while diff:
        step = 1 if diff > 0 else -1
        free = np.flatnonzero(~pinned & (lens + step >= 1)
                              & (lens + step <= max_group))
        take = rng.choice(free, size=min(abs(diff), len(free)),
                          replace=False)
        lens[take] += step
        diff -= step * len(take)
    return lens


def row_queries(lens: np.ndarray, seed: int, stream: int = 0) -> np.ndarray:
    """The query of every row, in the order the rows are handed over:
    whole queries shuffled, one in ``INTERLEAVE_EVERY`` riffled with its
    neighbour."""
    rng = _rng(seed, _ORDER_KEY, stream)
    queries = len(lens)
    place = rng.permutation(queries).astype(np.float64)   # of each query
    # a riffled query shares its neighbour's place: their rows' keys mix
    by_place = np.argsort(place)
    first = by_place[:-1:INTERLEAVE_EVERY]
    place[by_place[1::INTERLEAVE_EVERY][:len(first)]] = place[first]
    qid = np.repeat(np.arange(queries, dtype=np.int64), lens)
    key = place[qid] + rng.random(len(qid))
    return qid[np.argsort(key, kind="stable")]


def _latent(x0, x1, x2, x3, x4, offset, noise):
    return (0.9 * x0 + 0.7 * x1 * x2 + 0.5 * x3 - 0.4 * np.abs(x4)
            + offset + 0.7 * noise)


def grade_thresholds(seed: int) -> np.ndarray:
    """The four cuts of the latent score, from the seed alone."""
    rng = _rng(seed, *_RULE_KEY)
    z = rng.standard_normal((7, _CALIBRATION_ROWS))
    u = _SIGMA_OFFSET * z[5]
    score = _latent(z[0] + 0.5 * u, z[1], z[2], z[3], z[4], u, z[6])
    return np.quantile(score, np.cumsum(GRADE_SHARES)[:-1])


def mslr_like(queries: int, rows: Optional[int], features: int, seed: int,
              stream: int = 0, max_group: int = 1251
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(X [rows, features] float32, relevance [rows] float32 in 0..4,
    qid [rows] int64)``.  ``stream`` names an independent draw of the
    same rule (0 = training queries, 1 = held-out)."""
    if features < 6:
        raise ValueError("the relevance rule reads six columns")
    lens = group_sizes(queries, rows, max_group, stream)
    qid = row_queries(lens, seed, stream)
    rows = len(qid)
    offset_of = (_SIGMA_OFFSET * _rng(seed, _OFFSET_KEY, stream)
                 .standard_normal(queries)).astype(np.float32)
    cuts = grade_thresholds(seed).astype(np.float32)
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)
    starts = list(range(0, rows, BLOCK_ROWS))
    children = np.random.SeedSequence(
        entropy=int(seed), spawn_key=(int(stream),)).spawn(len(starts))

    def fill(job):
        lo, child = job
        hi = min(lo + BLOCK_ROWS, rows)
        rng = np.random.default_rng(child)
        rng.standard_normal(out=X[lo:hi], dtype=np.float32)
        u = offset_of[qid[lo:hi]]
        X[lo:hi, 0] += 0.5 * u
        X[lo:hi, 5] = u + 0.3 * X[lo:hi, 5]
        noise = rng.standard_normal(hi - lo, dtype=np.float32)
        b = X[lo:hi]
        score = _latent(b[:, 0], b[:, 1], b[:, 2], b[:, 3], b[:, 4], u, noise)
        y[lo:hi] = np.searchsorted(cuts, score, side="right")

    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(fill, zip(starts, children)))
    return X, y, qid
