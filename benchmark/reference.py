"""The plain reference: the same semantics in float64 numpy.

Imports nothing of the program.  Every function takes plain host arrays.
``precision="bfloat16"`` is the CONTROL: the same arithmetic with its
inputs and its running sums rounded to bfloat16 — the step below the
float32 that the configurations state — which every comparison built on
these functions has to reject (``tests/test_correct.py``).  Where the
configuration itself states bfloat16 (gradients on their way into the
histogram kernels), ``precision="float8"`` is the step below that.

Tree arrays are the model's own format: ``feat``/``thr`` ``[depth, half]``
(node ``i`` of level ``l`` at ``[l, i]``), ``leaf`` ``[2**depth]``; a row
goes right where ``bin > thr``; ``bin`` = number of cuts ``<= x``.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Tuple

import numpy as np

#: the reference runs after every window, so it is spread over a few
#: threads (numpy's loops release the interpreter lock); the arithmetic
#: and its order are those of the plain loop
_THREADS = 8
#: rows a thread descends at a time
_ROW_CHUNK = 1 << 20


def _pmap(fn, items):
    items = list(items)
    if len(items) < 2:
        return [fn(it) for it in items]
    with ThreadPoolExecutor(_THREADS) as pool:
        return list(pool.map(fn, items))

#: rows summed exactly (float64) between two roundings of a bfloat16
#: running sum: one row tile of the histogram kernels
_BF16_TILE = 16384


def to_bf16(a) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), returned as float64."""
    f = np.ascontiguousarray(a, dtype=np.float32)
    u = f.view(np.uint32).astype(np.uint64)
    u = ((u + 0x7FFF + ((u >> 16) & 1)) >> 16) << 16
    return u.astype(np.uint32).view(np.float32).astype(np.float64).reshape(
        np.shape(a))


def to_fp8(a) -> np.ndarray:
    """Round to the nearest float8 e4m3 value (3 mantissa bits, spacing
    2**-9 below 2**-6, no overflow handling: gradients stay inside
    (-1, 1)), returned as float64 — the step below bfloat16."""
    a = np.asarray(a, np.float64)
    m, e = np.frexp(a)                       # a = m * 2**e, 0.5 <= |m| < 1
    e = np.maximum(e, -5)                    # subnormals share one spacing
    return np.ldexp(np.round(np.ldexp(a, 4 - e)), e - 4)


def _round_inputs(weights: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float64":
        return np.asarray(weights, np.float64)
    return to_fp8(weights) if precision == "float8" else to_bf16(weights)


def _sum_by(index: np.ndarray, rounded: np.ndarray, size: int,
            precision: str) -> np.ndarray:
    """``out[k] = sum(rounded[index == k])`` of weights already rounded by
    :func:`_round_inputs`: exact (float64), or for the ``bfloat16``
    control a bfloat16 running sum over row tiles."""
    if precision != "bfloat16":
        return np.bincount(index, weights=rounded, minlength=size)
    acc = np.zeros(size)
    for lo in range(0, len(index), _BF16_TILE):
        part = np.bincount(index[lo:lo + _BF16_TILE],
                           weights=rounded[lo:lo + _BF16_TILE],
                           minlength=size)
        acc = to_bf16(acc + to_bf16(part))
    return acc


# -- objective ---------------------------------------------------------------

def logistic_grad_hess(margin: np.ndarray, y: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray]:
    p = 1.0 / (1.0 + np.exp(-np.asarray(margin, np.float64)))
    return p - y, p * (1.0 - p)


def logloss(margin: np.ndarray, y: np.ndarray) -> float:
    m = np.asarray(margin, np.float64)
    # log(1 + exp(-m)) for y = 1, log(1 + exp(m)) for y = 0, stably
    z = np.where(y > 0.5, -m, m)
    return float(np.mean(np.maximum(z, 0) + np.log1p(np.exp(-np.abs(z)))))


def auc(score: np.ndarray, y: np.ndarray) -> float:
    """ROC AUC by the rank sum, midranks for ties."""
    score = np.asarray(score, np.float64)
    order = np.argsort(score, kind="mergesort")
    s = score[order]
    ranks = np.empty(len(s))
    # midranks: average position of each run of equal scores
    bounds = np.flatnonzero(np.r_[True, s[1:] != s[:-1], True])
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        ranks[lo:hi] = 0.5 * (lo + hi - 1) + 1.0
    pos = y[order] > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    if not n_pos or not n_neg:
        return 0.5
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))


# -- trees -------------------------------------------------------------------

def descend_binned(bins_t: np.ndarray, feat: np.ndarray, thr: np.ndarray
                   ) -> np.ndarray:
    """Leaf index of every row of a feature-major binned matrix
    ``[F, n]`` under one tree."""
    def chunk(lo):
        part = bins_t[:, lo:lo + _ROW_CHUNK]
        rows = np.arange(part.shape[1])
        node = np.zeros(part.shape[1], np.int64)
        for level in range(feat.shape[0]):
            row_bin = part[feat[level][node], rows]
            node = 2 * node + (row_bin > thr[level][node])
        return node

    return np.concatenate(_pmap(chunk, range(0, bins_t.shape[1], _ROW_CHUNK)))


def descend_raw(X: np.ndarray, cuts: np.ndarray, feat: np.ndarray,
                thr: np.ndarray) -> np.ndarray:
    """Leaf index of raw rows ``[n, F]``: ``bin > thr`` is ``x >=
    cuts[f, thr]`` (bin = number of cuts <= x); ``thr`` past the last cut
    is the program's "no split, all left"."""
    n = X.shape[0]
    rows = np.arange(n)
    n_cuts = cuts.shape[1]
    node = np.zeros(n, np.int64)
    for level in range(feat.shape[0]):
        f = feat[level][node]
        t = thr[level][node]
        edge = np.where(t < n_cuts, cuts[f, np.minimum(t, n_cuts - 1)],
                        np.inf)
        node = 2 * node + (X[rows, f] >= edge)
    return node


def ensemble_margin(X: np.ndarray, cuts: np.ndarray, trees, base_score: float,
                    precision: str = "float64") -> np.ndarray:
    """Raw margin of raw rows under a list of trees — a plain descent, one
    tree after another.  The control rounds every leaf value and every
    partial sum to bfloat16."""
    cuts = np.asarray(cuts, np.float64)
    X = np.asarray(X, np.float64)
    margin = np.full(X.shape[0], float(base_score))
    nodes = _pmap(lambda t: descend_raw(X, cuts, np.asarray(t["feat"]),
                                        np.asarray(t["thr"])), trees)
    for t, node in zip(trees, nodes):
        add = np.asarray(t["leaf"], np.float64)[node]
        if precision == "float64":
            margin = margin + add
        else:
            margin = to_bf16(margin + to_bf16(add))
    return margin


def sigmoid(m: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-m))


def leaf_values(node: np.ndarray, g: np.ndarray, h: np.ndarray, n_leaf: int,
                eta: float, lam: float, precision: str = "float64"
                ) -> np.ndarray:
    """``-eta * G / (H + lambda)`` of every leaf, over the rows routed there."""
    G = _sum_by(node, _round_inputs(g, precision), n_leaf, precision)
    H = _sum_by(node, _round_inputs(h, precision), n_leaf, precision)
    return -eta * G / (H + lam)


def worst_leaf_gap(got: np.ndarray, ref: np.ndarray) -> float:
    """Widest gap between two leaf vectors, each measured against the
    reference's own leaf or the median leaf, whichever is larger (a leaf
    whose gradients cancel is all but zero)."""
    ref = np.asarray(ref, np.float64)
    scale = np.maximum(np.abs(ref), np.median(np.abs(ref)))
    return float(np.max(np.abs(np.asarray(got, np.float64) - ref) / scale))


def root_histogram(bins_t: np.ndarray, g: np.ndarray, h: np.ndarray,
                   n_bins: int, precision: str = "float64"
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """``[F, n_bins]`` gradient and hessian sums of the root node."""
    F = bins_t.shape[0]
    G = np.empty((F, n_bins))
    H = np.empty((F, n_bins))
    g, h = _round_inputs(g, precision), _round_inputs(h, precision)
    for f in range(F):
        idx = bins_t[f].astype(np.intp)
        G[f] = _sum_by(idx, g, n_bins, precision)
        H[f] = _sum_by(idx, h, n_bins, precision)
    return G, H


def root_histogram_by_class(bins_t: np.ndarray, cls: np.ndarray,
                            g_of: np.ndarray, h_of: np.ndarray, n_bins: int
                            ) -> Tuple[np.ndarray, np.ndarray]:
    """The same sums where rows fall into a few classes of equal (g, h),
    as they do before the first tree (one class per label): integer
    counts per (class, bin), then one float64 product — exact and several
    times cheaper than a weighted bincount over all rows."""
    F = bins_t.shape[0]
    n_cls = len(g_of)
    G = np.empty((F, n_bins))
    H = np.empty((F, n_bins))
    off = cls.astype(np.int32) * n_bins

    def one(f):
        counts = np.bincount(bins_t[f] + off, minlength=n_cls * n_bins
                             ).reshape(n_cls, n_bins).astype(np.float64)
        G[f] = g_of @ counts
        H[f] = h_of @ counts

    _pmap(one, range(F))
    return G, H


def split_gains(G: np.ndarray, H: np.ndarray, lam: float,
                min_child_weight: float) -> np.ndarray:
    """XGBoost's split gain ``0.5 * (GL^2/(HL+l) + GR^2/(HR+l) -
    G^2/(H+l))`` of every (feature, threshold): ``[F, n_bins - 1]``,
    ``-inf`` where a child is lighter than ``min_child_weight``."""
    gl = np.cumsum(G, axis=1)[:, :-1]
    hl = np.cumsum(H, axis=1)[:, :-1]
    gt = G.sum(axis=1, keepdims=True)
    ht = H.sum(axis=1, keepdims=True)
    gr, hr = gt - gl, ht - hl
    gain = 0.5 * (gl ** 2 / (hl + lam) + gr ** 2 / (hr + lam)
                  - gt ** 2 / (ht + lam))
    return np.where((hl >= min_child_weight) & (hr >= min_child_weight),
                    gain, -np.inf)


# -- ingest ------------------------------------------------------------------

def quantile_cuts(col: np.ndarray, n_bins: int, n_summary: int,
                  precision: str = "float64") -> np.ndarray:
    """Cut points of one feature as the configuration defines them: an
    ``n_summary``-point even quantile summary of the column, and the
    ``n_bins - 1`` interior quantiles of that summary, made strictly
    increasing by the smallest bumps (``max(|c| * 1e-6, 1e-6)``)."""
    col = np.asarray(col, np.float64)
    summary = np.quantile(col, np.linspace(0.0, 1.0, n_summary))
    cuts = np.quantile(summary, np.linspace(0.0, 1.0, n_bins + 1)[1:-1])
    eps = np.maximum(np.abs(cuts) * 1e-6, 1e-6)
    E = np.cumsum(eps) - eps
    cuts = E + np.maximum.accumulate(cuts - E)
    return to_bf16(cuts) if precision != "float64" else cuts


def bin_rows(X: np.ndarray, cuts: np.ndarray, precision: str = "float64"
             ) -> np.ndarray:
    """``[n, F]`` bins of raw rows: the number of cuts ``<= x``.  The
    control rounds the rows to bfloat16 first."""
    if precision != "float64":
        X = to_bf16(X)
    out = np.empty(X.shape, np.int64)
    for f in range(X.shape[1]):
        out[:, f] = np.searchsorted(np.asarray(cuts[f], np.float64),
                                    np.asarray(X[:, f], np.float64),
                                    side="right")
    return out
