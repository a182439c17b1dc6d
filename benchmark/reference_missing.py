"""The plain reference of the missing-value configuration: the same
semantics in float64 numpy, where NaN means missing.

Imports nothing of the program.  What ``reference.py`` states for dense
rows holds here but for what a missing value changes, and the functions
that it does not change are that file's own, imported as they are
(objective, loss, AUC, leaf values, the root histogram, the roundings of
the controls).  What changes:

* **cuts** are taken over the values a column HAS: an ``n_summary``-point
  summary of the non-NaN values by the midpoint rule (value ``k`` of the
  ``c`` sorted values sits at probability ``(k + 0.5) / c``; the summary
  is read at ``q = j / (n_summary - 1)`` by linear interpolation, flat
  beyond the first and the last value), then the ``n_bins - 2`` interior
  quantiles of the summary, made strictly increasing as the dense cuts
  are.  ``n_bins - 1`` value bins; bin ``n_bins - 1`` is reserved.
* **bin** ``b`` of a value is the number of cuts ``<= x``, in
  ``0 .. n_bins - 2``; of a NaN, ``n_bins - 1``.
* a node's histogram holds ``(G_b, H_b)`` of every value bin and the
  missing mass ``(G_m, H_m)`` in the reserved bin.  Every threshold
  ``t <= n_bins - 2`` of every feature is scored twice, the missing mass
  on the right (``GL = sum_{b <= t} G_b``) and on the left
  (``GL + G_m``), each under ``min_child_weight`` on both children; the
  best (feature, threshold, direction) wins, the first in (feature,
  threshold) order among equals, and at equal gain the missing rows go
  right.
* a row whose bin is the reserved one follows its node's direction
  (``dir`` 1 = left, 0 = right); every other row goes right iff
  ``b > t``.

Tree arrays are the model's own format (``feat``, ``thr``, ``dir``
``[depth, half]``, ``leaf`` ``[2**depth]``).  The CONTROLS that every
comparison built on these functions has to reject
(``tests/test_missing.py``): ``precision="bfloat16"`` as in
``reference.py``; ``alias_missing=True``, NaN binned as the top value
bin (what plain binning does to it); ``force_left=True``, every
direction fixed to the left (a tree that does not learn it).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from benchmark.reference import (_ROW_CHUNK, _pmap, auc,  # noqa: F401
                                 leaf_values, logistic_grad_hess, logloss,
                                 root_histogram, root_histogram_by_class,
                                 sigmoid, to_bf16)


# -- ingest ------------------------------------------------------------------

def finite_summary(col: np.ndarray, n_summary: int) -> np.ndarray:
    """The midpoint-rule summary of a column's non-NaN values."""
    v = np.sort(np.asarray(col, np.float64)[~np.isnan(col)])
    c = len(v)
    return np.interp(np.linspace(0.0, 1.0, n_summary),
                     (np.arange(c) + 0.5) / c, v)


def quantile_cuts(col: np.ndarray, n_bins: int, n_summary: int,
                  precision: str = "float64") -> np.ndarray:
    """The ``n_bins - 2`` cut points of one feature (``n_bins - 1`` value
    bins and the reserved one)."""
    summary = finite_summary(col, n_summary)
    cuts = np.quantile(summary, np.linspace(0.0, 1.0, n_bins)[1:-1])
    eps = np.maximum(np.abs(cuts) * 1e-6, 1e-6)
    E = np.cumsum(eps) - eps
    cuts = E + np.maximum.accumulate(cuts - E)
    return to_bf16(cuts) if precision != "float64" else cuts


def bin_rows(X: np.ndarray, cuts: np.ndarray, precision: str = "float64",
             alias_missing: bool = False) -> np.ndarray:
    """``[n, F]`` bins of raw rows: the number of cuts ``<= x``; NaN in
    the reserved bin ``cuts.shape[1] + 1``.  The controls: rows rounded
    to bfloat16 first; NaN left where ``searchsorted`` puts it, in the
    top value bin."""
    if precision != "float64":
        X = np.where(np.isnan(X), np.nan, to_bf16(np.nan_to_num(X)))
    miss_bin = cuts.shape[1] + 1
    out = np.empty(X.shape, np.int64)
    for f in range(X.shape[1]):
        col = np.asarray(X[:, f], np.float64)
        out[:, f] = np.searchsorted(np.asarray(cuts[f], np.float64), col,
                                    side="right")
        if not alias_missing:
            out[np.isnan(col), f] = miss_bin
    return out


# -- trees -------------------------------------------------------------------

def split_gains(G: np.ndarray, H: np.ndarray, lam: float,
                min_child_weight: float) -> np.ndarray:
    """XGBoost's split gain of every (direction, feature, threshold) of
    one node: ``[2, F, n_bins - 1]``, direction 0 the missing mass on the
    right, 1 on the left; ``-inf`` where a child is lighter than
    ``min_child_weight``.  ``G``, ``H``: ``[F, n_bins]``, the reserved
    bin last."""
    gm, hm = G[:, -1:], H[:, -1:]
    gl = np.cumsum(G[:, :-1], axis=1)
    hl = np.cumsum(H[:, :-1], axis=1)
    gt = G.sum(axis=1, keepdims=True)
    ht = H.sum(axis=1, keepdims=True)

    def side(gl_, hl_):
        gr_, hr_ = gt - gl_, ht - hl_
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = 0.5 * (gl_ ** 2 / (hl_ + lam) + gr_ ** 2 / (hr_ + lam)
                          - gt ** 2 / (ht + lam))
        return np.where((hl_ >= min_child_weight)
                        & (hr_ >= min_child_weight), gain, -np.inf)

    return np.stack([side(gl, hl), side(gl + gm, hl + hm)])


def best_split(gains: np.ndarray) -> Tuple[int, int, int]:
    """(feature, threshold, dir) of the best gain: the first (feature,
    threshold) among equals, and the right (``dir`` 0) at equal gain."""
    both = np.maximum(gains[0], gains[1])
    f, t = np.unravel_index(int(np.argmax(both)), both.shape)
    return int(f), int(t), int(gains[1, f, t] > gains[0, f, t])


def leaf_gaps(got: np.ndarray, ref: np.ndarray, rows: np.ndarray
              ) -> Tuple[float, float]:
    """Two gaps between a tree's leaves and the reference's: the WORST
    leaf's, and the mean over the ROWS (each leaf's gap weighted by the
    rows it holds).  Each gap is measured against the reference's own
    leaf or the median of the leaves that hold rows, whichever is larger
    (``reference.worst_leaf_gap``'s scale, where leaves may be EMPTY:
    with one row in 170 a positive, a node of negatives alone has no
    split worth its gain, its rows all go left, and the leaves to its
    right hold no row and are exactly 0 — more than half of a deep
    tree's leaves, so the median over all leaves is 0 and no scale).

    Why both.  Before the first tree every gradient is +-0.5 and every
    float32 sum exact: the worst leaf reads 1e-7.  From the second tree
    on the gradients of 1.18M rows are all but 0.6% of one sign, a
    node's sum is ~5e5 with a float32 step of 0.03-0.06, and a right
    child is its parent less its sibling: a leaf of a dozen rows, eight
    subtractions below the root, carries an absolute error of a few
    steps of the ROOT's sum, which is percents of its own value — what
    float32 sums, the precision the configuration states, give on
    one-sided gradients.  The worst leaf of tree 1 therefore reads as
    wide in a sound run as under the controls; the mean over the rows
    does not (a leaf's error is absolute, so it falls with its rows)."""
    ref = np.asarray(ref, np.float64)
    live = np.abs(ref[ref != 0])
    scale = np.maximum(np.abs(ref), np.median(live) if len(live) else 1.0)
    gap = np.abs(np.asarray(got, np.float64) - ref) / scale
    return float(gap.max()), float(gap @ rows / max(rows.sum(), 1))


def _directions(tree, force_left: bool) -> np.ndarray:
    d = np.asarray(tree["dir"])
    return np.ones_like(d) if force_left else d


def descend_binned(bins_t: np.ndarray, tree, miss_bin: int,
                   force_left: bool = False) -> np.ndarray:
    """Leaf index of every row of a feature-major binned matrix
    ``[F, n]`` under one tree; ``miss_bin`` is the reserved bin."""
    feat, thr = np.asarray(tree["feat"]), np.asarray(tree["thr"])
    dirv = _directions(tree, force_left)

    def chunk(lo):
        part = bins_t[:, lo:lo + _ROW_CHUNK]
        rows = np.arange(part.shape[1])
        node = np.zeros(part.shape[1], np.int64)
        for level in range(feat.shape[0]):
            row_bin = part[feat[level][node], rows]
            right = np.where(row_bin == miss_bin, dirv[level][node] == 0,
                             row_bin > thr[level][node])
            node = 2 * node + right
        return node

    return np.concatenate(_pmap(chunk, range(0, bins_t.shape[1], _ROW_CHUNK)))


def descend_raw(X: np.ndarray, cuts: np.ndarray, tree,
                force_left: bool = False) -> np.ndarray:
    """Leaf index of raw rows ``[n, F]``: a value goes right iff
    ``x >= cuts[f, thr]`` (``thr`` past the last cut: never), a NaN by
    the node's direction."""
    feat, thr = np.asarray(tree["feat"]), np.asarray(tree["thr"])
    dirv = _directions(tree, force_left)
    n = X.shape[0]
    rows = np.arange(n)
    n_cuts = cuts.shape[1]
    node = np.zeros(n, np.int64)
    for level in range(feat.shape[0]):
        f = feat[level][node]
        t = thr[level][node]
        x = X[rows, f]
        edge = np.where(t < n_cuts, cuts[f, np.minimum(t, n_cuts - 1)],
                        np.inf)
        node = 2 * node + np.where(np.isnan(x), dirv[level][node] == 0,
                                   x >= edge)
    return node


def ensemble_margin(X: np.ndarray, cuts: np.ndarray, trees, base_score: float,
                    precision: str = "float64", force_left: bool = False
                    ) -> np.ndarray:
    """Raw margin of raw rows under a list of trees — a plain descent, one
    tree after another.  The bfloat16 control rounds every leaf value and
    every partial sum."""
    cuts = np.asarray(cuts, np.float64)
    X = np.asarray(X, np.float64)
    margin = np.full(X.shape[0], float(base_score))
    nodes = _pmap(lambda t: descend_raw(X, cuts, t, force_left), trees)
    for t, node in zip(trees, nodes):
        add = np.asarray(t["leaf"], np.float64)[node]
        if precision == "float64":
            margin = margin + add
        else:
            margin = to_bf16(margin + to_bf16(add))
    return margin
