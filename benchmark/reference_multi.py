"""The plain reference for several classes: XGBoost's ``multi:softmax``
(``SoftmaxMultiClassObj``) in float64 numpy, row by row.

Imports nothing of the program and nothing of a device: a softmax over
the K margins of a row, ``g = p - 1[y = c]``, ``h = max(2 p (1 - p),
1e-6)`` (upstream's factor 2; its floor is 1e-16 there, 1e-6 in this
library — the configuration's ``assumed`` says so), and class c's tree of
a round grown from column c of those.  No scan over the classes, no
class-major layout, no kernel: margins are ``[n, K]``, a row a row.  The
trees' histogram, gain, leaf and descent are ``reference.py``'s own.

Tree arrays are the model's format with the class first: a round is one
dict of ``feat``/``thr``/``gain`` ``[K, depth, half]`` and ``leaf``
``[K, 2**depth]``; :func:`class_tree` cuts class c's tree out of it.

``control`` puts a wrong objective in the softmax's place, for the
self-tests and ``tests/multi_on_chip.py`` (each has to leave a limit):
``ovr`` — K one-vs-rest sigmoids, no row couples its classes; ``hess1`` —
the hessian without its factor 2; ``bf16_margin`` — the margins rounded
to bfloat16 before the softmax.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

from benchmark import reference as ref

#: the library's floor of a hessian (XGBoost's kRtEps is 1e-16)
HESS_FLOOR = 1e-6
GRAD_CONTROLS = ("ovr", "hess1", "bf16_margin")


def softmax(margin: np.ndarray) -> np.ndarray:
    """Row-wise softmax of ``[n, K]`` margins, stably."""
    m = np.asarray(margin, np.float64)
    e = np.exp(m - m.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)


def softmax_grad_hess(margin: np.ndarray, y: np.ndarray,
                      control: str = "") -> Tuple[np.ndarray, np.ndarray]:
    """``[n, K]`` gradients and hessians of the multiclass log loss at
    ``[n, K]`` margins and labels ``y`` in ``0..K-1``."""
    m = np.asarray(margin, np.float64)
    if control == "bf16_margin":
        m = ref.to_bf16(m)
    p = ref.sigmoid(m) if control == "ovr" else softmax(m)
    hit = np.arange(m.shape[1])[None, :] == np.asarray(y, np.int64)[:, None]
    factor = 1.0 if control == "hess1" else 2.0
    return p - hit, np.maximum(factor * p * (1.0 - p), HESS_FLOOR)


def mlogloss(margin: np.ndarray, y: np.ndarray) -> float:
    """Mean of ``-log p[y]`` over the rows."""
    m = np.asarray(margin, np.float64)
    m = m - m.max(axis=1, keepdims=True)
    logp = m - np.log(np.exp(m).sum(axis=1, keepdims=True))
    return float(-np.mean(logp[np.arange(len(y)), np.asarray(y, np.int64)]))


def merror(margin: np.ndarray, y: np.ndarray) -> float:
    """Share of the rows whose largest margin is not their label's."""
    return float(np.mean(np.argmax(margin, axis=1) != np.asarray(y, np.int64)))


def class_tree(round_trees: Dict[str, np.ndarray], c: int
               ) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v)[c] for k, v in round_trees.items()}


def class_bin_counts(bins_t: np.ndarray, y: np.ndarray, n_class: int,
                     n_bins: int) -> np.ndarray:
    """``[F, K, n_bins]`` rows of each (label, bin) of every feature:
    integer counts, from which the round-0 histograms of all K trees are
    one float64 product each (every row of a label has the same
    gradient before the first tree) — ``reference.py``'s
    ``root_histogram_by_class`` with the counts kept."""
    F = bins_t.shape[0]
    out = np.empty((F, n_class, n_bins), np.float64)
    off = np.asarray(y, np.int32) * n_bins

    def one(f):
        out[f] = np.bincount(bins_t[f] + off, minlength=n_class * n_bins
                             ).reshape(n_class, n_bins)

    ref._pmap(one, range(F))
    return out


def ensemble_margin(X: np.ndarray, cuts: np.ndarray,
                    rounds: Sequence[Dict[str, np.ndarray]], base_score: float,
                    precision: str = "float64", shift: int = 0) -> np.ndarray:
    """``[n, K]`` raw margins of raw rows: class c's trees, one a round,
    descended plainly and added onto column ``c`` (``shift`` = 1 is the
    control that adds them onto column c + 1)."""
    K = int(np.asarray(rounds[0]["leaf"]).shape[0])
    out = np.empty((X.shape[0], K), np.float64)
    for c in range(K):
        out[:, (c + shift) % K] = ref.ensemble_margin(
            X, cuts, [class_tree(r, c) for r in rounds], base_score,
            precision)
    return out
