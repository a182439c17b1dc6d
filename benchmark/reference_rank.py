"""The plain reference of the ranking cells: LambdaMART's gradient as a
loop over queries in float64 numpy, then ``reference.py``'s own
histogram, gain, leaf and descent for the trees (imported, not copied).

Imports nothing of the program, and has nothing of its layout in it: no
bucket, no block, no pad.  For every pair of ONE query with ``rel_i >
rel_j``:

    p = sigmoid(s_j - s_i)
    w = |2^rel_i - 2^rel_j| * |1/log2(2 + rank_i) - 1/log2(2 + rank_j)|
        / IDCG                           (w = 1 under ``rank:pairwise``)
    g_i -= p * w;   g_j += p * w;   h_i, h_j += p * (1 - p) * w

and ``h >= 1e-16``.  Ranks are taken from the current scores over the
WHOLE query, exp2 gain, no truncation level, no pair sampling, no
normalisation by the group.  THE RULE FOR TIES: descending score, then
position in the query (the order its documents were handed over in),
stable — at round 0 every score is ``base_score`` and the rule alone
decides the ranks.  A query of one document, or of one relevance level,
has no pair: ``g = 0``, ``h = 1e-16``.

``control=`` puts a fault in the gradient's place, for the self-tests and
``tests/rank_on_chip.py``: every comparison built on this file has to
reject each of them.
"""

from __future__ import annotations

import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor
from typing import Callable, Optional, Tuple

import numpy as np

from benchmark.reference import to_bf16

#: the loop over the queries runs after every window: above this many
#: pair slots it is dealt to a few PROCESSES, in runs of whole queries
#: (threads make it slower: the pair lists' indexing holds the
#: interpreter lock).  The arithmetic of a query, and its order, are the
#: plain loop's either way.
_SPREAD_FROM_PAIRS = 50_000_000
_PROCESSES = 12
#: pair terms summed exactly between two roundings of the bfloat16
#: control's running sum
_BF16_PAIR_TILE = 8
H_FLOOR = 1e-16

CONTROLS = ("truncate128", "pairwise", "reverse_ties", "pads_first",
            "bfloat16_pairs")


def query_bounds(qid: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """``(order, bounds)``: the rows in query order — one stable sort, so
    a query's documents keep the order they were handed over in — and the
    first row of every query in that order, with the row count last."""
    order = np.argsort(qid, kind="stable")
    qs = np.asarray(qid)[order]
    first = np.flatnonzero(np.r_[True, qs[1:] != qs[:-1]])
    return order, np.r_[first, len(qs)]


def ranks_of(s: np.ndarray, reverse_ties: bool = False) -> np.ndarray:
    """Rank of each document of one query (0 = best): descending score,
    then position in the query."""
    if reverse_ties:                     # the control: then LAST position
        order = np.argsort(-s[::-1], kind="stable")
        order = len(s) - 1 - order
    else:
        order = np.argsort(-s, kind="stable")
    ranks = np.empty(len(s), np.int64)
    ranks[order] = np.arange(len(s))
    return ranks


def _bf16_sum(terms: np.ndarray, axis: int) -> np.ndarray:
    """A bfloat16 running sum along ``axis`` of terms rounded to bfloat16."""
    terms = np.moveaxis(to_bf16(terms), axis, -1)
    acc = np.zeros(terms.shape[:-1])
    for lo in range(0, terms.shape[-1], _BF16_PAIR_TILE):
        acc = to_bf16(acc + to_bf16(
            terms[..., lo:lo + _BF16_PAIR_TILE].sum(axis=-1)))
    return acc


def query_grad_hess(s: np.ndarray, rel: np.ndarray, weight: str = "ndcg",
                    control: Optional[str] = None, rank_offset: int = 0
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """``(g, h)`` of ONE query's documents, in the order they were handed
    over.  ``rank_offset`` is the ``pads_first`` control's: that many pad
    slots ranked ahead of every document."""
    s = np.asarray(s, np.float64)
    rel = np.asarray(rel, np.float64)
    n = len(s)
    g = np.zeros(n)
    h = np.zeros(n)
    if control == "truncate128":         # the old layout's shortcut
        s, rel = s[:128], rel[:128]
    # every pair (i, j) of the query with rel_i > rel_j, once
    i, j = np.nonzero(rel[:, None] > rel[None, :])
    if len(i):
        p = 1.0 / (1.0 + np.exp(s[i] - s[j]))
        rho = p * (1.0 - p)
        if weight == "ndcg" and control != "pairwise":
            ranks = ranks_of(s, control == "reverse_ties") + (
                rank_offset if control == "pads_first" else 0)
            disc = 1.0 / np.log2(2.0 + ranks)
            gain = np.exp2(rel) - 1.0
            ideal = np.sort(rel)[::-1]
            idcg = float(((np.exp2(ideal) - 1.0)
                          / np.log2(2.0 + np.arange(len(ideal)))).sum())
            w = np.abs(gain[i] - gain[j]) * np.abs(disc[i] - disc[j]) / idcg
            p = p * w
            rho = rho * w
        m = len(s)
        if control == "bfloat16_pairs":
            # the pair terms as a [m, m] table, summed by a bfloat16
            # accumulator along either axis
            lam, cur = np.zeros((m, m)), np.zeros((m, m))
            lam[i, j], cur[i, j] = p, rho
            g[:m] = -_bf16_sum(lam, 1) + _bf16_sum(lam, 0)
            h[:m] = _bf16_sum(cur, 1) + _bf16_sum(cur, 0)
        else:
            g[:m] = (-np.bincount(i, weights=p, minlength=m)
                     + np.bincount(j, weights=p, minlength=m))
            h[:m] = (np.bincount(i, weights=rho, minlength=m)
                     + np.bincount(j, weights=rho, minlength=m))
    return g, np.maximum(h, H_FLOOR)


def _queries_grad_hess(margin, rel, bounds, weight, control, width_of):
    """The loop: ``(g, h)`` of the rows of some whole queries, ``bounds``
    counted from the first of them."""
    g = np.zeros(len(margin))
    h = np.full(len(margin), H_FLOOR)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        lo, hi = int(lo), int(hi)
        offset = (width_of(hi - lo) - (hi - lo)
                  if control == "pads_first" else 0)
        g[lo:hi], h[lo:hi] = query_grad_hess(
            margin[lo:hi], rel[lo:hi], weight, control, offset)
    return g, h


def _job(args):
    return _queries_grad_hess(*args)


def lambda_grad_hess(margin: np.ndarray, rel: np.ndarray, bounds: np.ndarray,
                     weight: str = "ndcg", control: Optional[str] = None,
                     width_of: Optional[Callable[[int], int]] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """``(g, h)`` of rows in QUERY ORDER (``query_bounds``): the loop over
    the queries, one after another; a large table's queries are dealt to
    a few processes in runs of like pair counts (MSLR's 0.84G pair slots
    take 15 s in one).  ``width_of(n_docs)`` is the ``pads_first``
    control's: the padded width a faulty program would rank a query
    inside (a module-level function, so that a process can be told)."""
    margin = np.asarray(margin, np.float64)
    rel = np.asarray(rel, np.float64)
    bounds = np.asarray(bounds, np.int64)
    pairs = np.cumsum(np.diff(bounds) ** 2)
    workers = min(_PROCESSES, os.cpu_count() or 1)
    if workers < 2 or pairs[-1] < _SPREAD_FROM_PAIRS:
        return _queries_grad_hess(margin, rel, bounds, weight, control,
                                  width_of)
    # four runs a process, cut where the pair count passes each share
    cut = np.unique(np.r_[0, np.searchsorted(
        pairs, pairs[-1] * np.arange(1, 4 * workers) / (4 * workers)) + 1,
        len(bounds) - 1])
    jobs = [(margin[bounds[a]:bounds[b]], rel[bounds[a]:bounds[b]],
             bounds[a:b + 1] - bounds[a], weight, control, width_of)
            for a, b in zip(cut[:-1], cut[1:])]
    # spawn: a forked copy of a process that holds the chip is no place
    # to run in; these import numpy and this module, nothing else
    with ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn")) as pool:
        parts = list(pool.map(_job, jobs))
    return (np.concatenate([g for g, _ in parts]),
            np.concatenate([h for _, h in parts]))


def ndcg_at(scores: np.ndarray, rel: np.ndarray, bounds: np.ndarray,
            k: int = 10) -> float:
    """Mean NDCG@k over the queries of rows in query order, computed
    plainly: exp2 gain, log2 discount, ties by position; a query with no
    relevant document scores 1."""
    scores = np.asarray(scores, np.float64)
    rel = np.asarray(rel, np.float64)
    vals = []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        r = rel[lo:hi]
        kk = min(k, hi - lo)
        disc = 1.0 / np.log2(2.0 + np.arange(kk))
        top = np.argsort(-scores[lo:hi], kind="stable")[:kk]
        idcg = float(((np.exp2(np.sort(r)[::-1][:kk]) - 1.0) * disc).sum())
        dcg = float(((np.exp2(r[top]) - 1.0) * disc).sum())
        vals.append(1.0 if idcg == 0.0 else dcg / idcg)
    return float(np.mean(vals))
