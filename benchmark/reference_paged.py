"""The plain reference of the paged one-hot configuration: the same
semantics in float64 numpy, for rows that arrive as CSR pages and cuts
that come from a SKETCH.

Imports nothing of the program.  What ``reference.py`` states for dense
rows holds here; the trees, the objective, the loss, AUC, the root
histogram and ``bin_rows`` are that file's own, imported as they are
(the trees are grown on the program's own bins, so a table that arrived
in pages is judged as any other).  What is new:

* **a row** is what its CSR entries say and 0.0 everywhere else
  (:func:`densify`): an absent entry is a value, not a hole.
* **a sketched cut is judged by its RANK, not by its value.**  The
  program's cuts come from a streaming summary with a documented rank
  error eps (``ops/quantile.py`` of the repo: ``(ceil(log_C P) + 4) /
  (S - 1)`` for S summary points, P slabs, a C-ary merge ladder), so two
  sound sketches of one column differ in value by what the density
  allows and value-wise ``cuts_gap`` has no limit to give.  Cut ``j`` of
  ``n_bins - 1`` aims at the quantile ``(j + 1) / n_bins``; in the column
  sorted ONCE, exactly, it sits between the ranks ``#{x < c}`` and
  ``#{x <= c}``: its error is how far that interval lies from its aim,
  as a share of the rows (:func:`cut_rank_errors`; 0 where the aim is
  inside, as it is for a cut that sits ON a run of equal values — the
  number is taken on continuous columns, where the strictly increasing
  guard of the cuts moves nothing).
* **an indicator keeps its cut** where the values 0.0 and 1.0 fall in
  different bins (:func:`unsplit_indicators`): a level held by one row
  of a million still has a threshold to split on.

``precision="bfloat16"`` is ``reference.py``'s control (rows rounded
before they are binned); the controls of the sketch are in
``tests/paged_on_chip.py`` (cuts from the first slab alone, a 64-point
summary, an indicator's cuts pushed past 1.0).
"""

from __future__ import annotations

import math
from typing import Sequence

import numpy as np

from benchmark.reference import bin_rows  # noqa: F401 - the bins' rule


def densify(offset: np.ndarray, index: np.ndarray, value: np.ndarray,
            num_col: int, lo: int = 0, hi: int | None = None) -> np.ndarray:
    """Rows ``lo .. hi`` of a CSR block as float64 ``[hi - lo, num_col]``,
    0.0 where the block holds no entry."""
    hi = len(offset) - 1 if hi is None else hi
    a, b = int(offset[lo]), int(offset[hi])
    out = np.zeros((hi - lo, num_col))
    rows = np.repeat(np.arange(hi - lo), np.diff(offset[lo:hi + 1]))
    out[rows, index[a:b]] = value[a:b]
    return out


def sketch_eps(n_summary: int, slabs: int, buffer_pages: int = 32) -> float:
    """The rank error the library documents for a cut of its streaming
    sketch: one summary a slab, ``ceil(log_C P)`` ladder merges, the
    cross-level merge, the collapse and the re-quantile into bins, each
    at most ``1 / (S - 1)``."""
    ladder = math.ceil(math.log(max(slabs, 1), buffer_pages)) if slabs > 1 \
        else 0
    return (ladder + 4) / (n_summary - 1)


def cut_rank_errors(col_sorted: np.ndarray, cuts: np.ndarray) -> np.ndarray:
    """Per cut, the distance between its aim ``(j + 1) / n_bins`` and the
    ranks it takes in the exactly sorted column, as a share of the rows."""
    col_sorted = np.asarray(col_sorted, np.float64)
    cuts = np.asarray(cuts, np.float64)
    n = len(col_sorted)
    below = np.searchsorted(col_sorted, cuts, side="left") / n
    upto = np.searchsorted(col_sorted, cuts, side="right") / n
    aim = np.arange(1, len(cuts) + 1) / (len(cuts) + 1)
    return np.maximum(np.maximum(below - aim, aim - upto), 0.0)


def worst_rank_error(columns: Sequence[np.ndarray], cuts: np.ndarray
                     ) -> float:
    """The worst cut of a few columns (``cuts[k]`` belongs to
    ``columns[k]``), each column sorted here, in full, once."""
    return max(float(cut_rank_errors(np.sort(np.asarray(c, np.float64)),
                                     cuts[k]).max())
               for k, c in enumerate(columns))


def unsplit_indicators(cuts: np.ndarray, columns: np.ndarray) -> int:
    """How many of ``columns`` bin 0.0 and 1.0 alike under ``cuts``
    ``[F, n_bins - 1]`` (bin = number of cuts ``<= x``): an indicator
    without a threshold between its two values."""
    c = np.asarray(cuts, np.float64)[np.asarray(columns, np.int64)]
    return int(np.count_nonzero((c <= 0.0).sum(axis=1)
                                == (c <= 1.0).sum(axis=1)))
