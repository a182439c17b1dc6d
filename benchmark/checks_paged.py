"""The numbers that decide ``correct`` in the paged one-hot cells: what
the timed path produced, held against ``reference_paged.py`` (and,
for the trees, ``reference.py``'s grower over the program's own bins).
Pure functions of host arrays, as ``checks.py``'s are, so the self-tests
put each control in the program's place and see a number leave its
limit.  The limits are data, in the mix files (``checks.apply_limits``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from benchmark import checks, reference as ref, reference_paged as refp
from benchmark.datagen_onehot import NUMERIC
from benchmark.reference_missing import leaf_gaps


# -- the rows, as CSR blocks (offset, index, value, y) ----------------------

def numeric_columns(blocks, ids):
    """Columns ``ids`` (numeric: an entry in every row) of all the
    blocks, whole, as float64."""
    cols = {f: [] for f in ids}
    for _offset, index, value, _y in blocks:
        for f in ids:
            cols[f].append(value[index == f])
    return [np.concatenate(cols[f]).astype(np.float64) for f in ids]


def occupied_indicators(blocks, rows: int, features: int) -> np.ndarray:
    """The indicator columns that hold both values: set in at least one
    row and not in all."""
    count = np.zeros(features, np.int64)
    for _offset, index, _value, _y in blocks:
        count += np.bincount(index, minlength=len(count))
    cols = np.arange(NUMERIC, len(count))
    return cols[(count[cols] > 0) & (count[cols] < rows)]


def dense_rows(blocks, lo: int, k: int, features: int) -> np.ndarray:
    """Rows ``lo .. lo + k`` of the table, densified (float64)."""
    out, at = [], 0
    for offset, index, value, y in blocks:
        a, b = max(lo - at, 0), min(lo + k - at, len(y))
        if b > a:
            out.append(refp.densify(offset, index, value, features, a, b))
        at += len(y)
    return np.concatenate(out)


# -- ingest ----------------------------------------------------------------------

def cut_numbers(numeric_columns: Sequence[np.ndarray], numeric_ids,
                occupied_indicators, cuts: np.ndarray) -> Dict[str, float]:
    """The sketched cuts: the worst rank error over a few numeric columns
    (each handed over whole, as float values), and how many of the
    indicator columns that hold both values have no cut between them."""
    cuts = np.asarray(cuts, np.float64)
    return {
        "cuts_rank_error": refp.worst_rank_error(
            numeric_columns, cuts[np.asarray(numeric_ids, np.int64)]),
        "indicator_cuts_missing": refp.unsplit_indicators(
            cuts, occupied_indicators),
    }


def bins_mismatches(X_rows: np.ndarray, bins_rows_t: np.ndarray,
                    cuts: np.ndarray, precision: str = "float64") -> int:
    """``checks.bins_mismatches`` over ALL columns of a densified block;
    ``precision`` is the control's (rows rounded to bfloat16 first)."""
    want = ref.bin_rows(X_rows, cuts, precision)
    return int(np.count_nonzero(want.T != np.asarray(bins_rows_t)))


# -- boost -----------------------------------------------------------------------

def boost_tree_numbers(bins_t: np.ndarray, y: np.ndarray,
                       trees: Sequence[Dict[str, np.ndarray]],
                       cfg: Dict[str, Any],
                       worst_of_tree1: Optional[List[float]] = None
                       ) -> Dict[str, float]:
    """Trees 0 and 1 of one fit against the reference, on ALL the rows:
    ``checks.boost_tree_numbers``' root terms, and the leaves as the
    missing-value cell judges them (``reference_missing.leaf_gaps``):
    tree 0's worst leaf, tree 1's in the mean over the rows — with one
    row in 137 a positive, a leaf of a few rows holds |G| near 0 and its
    own relative gap says nothing (its worst leaf goes to
    ``worst_of_tree1`` for the record, compared with nothing)."""
    eta, lam = float(cfg["learning_rate"]), float(cfg["reg_lambda"])
    mcw, n_bins = float(cfg["min_child_weight"]), int(cfg["n_bins"])
    base = float(cfg["base_score"])
    y = np.asarray(y, np.float64)
    n_leaf = len(trees[0]["leaf"])
    out: Dict[str, float] = {}

    g0, h0 = ref.logistic_grad_hess(np.array([base, base]),
                                    np.array([0.0, 1.0]))
    G, H = ref.root_histogram_by_class(bins_t, (y > 0.5), g0, h0, n_bins)
    gains = ref.split_gains(G, H, lam, mcw)
    best = float(gains.max())
    f0, t0 = int(trees[0]["feat"][0, 0]), int(trees[0]["thr"][0, 0])
    at_split = float(gains[f0, t0]) if t0 < n_bins - 1 else 0.0
    out["tree0.root_gain_gap"] = (best - at_split) / abs(best)
    out["tree0.reported_gain_gap"] = (
        abs(float(trees[0]["gain"][0, 0]) - at_split) / abs(best))

    margin = np.full(len(y), base)
    for k in (0, 1):
        t = trees[k]
        g, h = ref.logistic_grad_hess(margin, y)
        node = ref.descend_binned(bins_t, t["feat"], t["thr"])
        leaf = ref.leaf_values(node, g, h, n_leaf, eta, lam)
        worst, by_rows = leaf_gaps(t["leaf"], leaf,
                                   np.bincount(node, minlength=n_leaf))
        if k == 0:
            out["tree0.leaf_gap"] = worst
        else:
            out["tree1.leaf_gap_by_rows"] = by_rows
            if worst_of_tree1 is not None:
                worst_of_tree1.append(worst)
        margin = margin + leaf[node]
    return out

