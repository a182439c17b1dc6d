"""The numbers that decide ``correct`` in the missing-value cells: what
the timed path produced, held against ``reference_missing.py``.  Pure
functions of host arrays, as ``checks.py``'s are, so the self-tests can
put each control in the program's place and see a number leave its limit:
the reference in bfloat16, NaN aliased into the top value bin, every
direction forced left.  The limits are data, in the mix files
(``checks.apply_limits``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from benchmark import reference_missing as ref


def _miss_bin(cfg: Dict[str, Any]) -> int:
    return int(cfg["n_bins"]) - 1


# -- ingest ----------------------------------------------------------------------

def cuts_gap(X: np.ndarray, cuts: np.ndarray, features: Sequence[int],
             cfg: Dict[str, Any]) -> float:
    """Widest gap between the program's cut points of a few features and
    the reference's cuts over the values those columns have, against
    ``max(|cut|, 1)``."""
    worst = 0.0
    for f in features:
        want = ref.quantile_cuts(X[:, f], int(cfg["n_bins"]),
                                 int(cfg["n_summary"]))
        gap = np.abs(np.asarray(cuts[f], np.float64) - want)
        worst = max(worst, float(np.max(gap / np.maximum(np.abs(want), 1.0))))
    return worst


def bin_numbers(X_rows: np.ndarray, bins_rows_t: np.ndarray,
                cuts: np.ndarray, cfg: Dict[str, Any]) -> Dict[str, float]:
    """A block of the binned matrix (feature-major ``[F, k]``) against the
    raw rows: entries that are not the number of the program's own cuts
    ``<= x`` (the reserved bin for NaN), and entries where "is NaN" and
    "is in the reserved bin" disagree."""
    want = ref.bin_rows(X_rows, cuts)
    got = np.asarray(bins_rows_t).T
    return {
        "bins_mismatches": int(np.count_nonzero(want != got)),
        "missing_bin_mismatches": int(np.count_nonzero(
            np.isnan(X_rows) != (got == _miss_bin(cfg)))),
    }


# -- boost -----------------------------------------------------------------------

def boost_tree_numbers(bins_t: np.ndarray, y: np.ndarray,
                       trees: Sequence[Dict[str, np.ndarray]],
                       cfg: Dict[str, Any],
                       worst_of_tree1: Optional[List[float]] = None
                       ) -> Dict[str, float]:
    """Trees 0 and 1 of one fit against the reference, on ALL the rows:
    ``checks.boost_tree_numbers`` with the missing mass in it.

    Tree 0: the root histogram (reserved bin included) is rebuilt from
    integer counts, every threshold scored with the missing mass on
    either side; the program's root (feature, threshold, direction) has
    to reach the reference's best gain, the gain it reports has to be the
    reference's for that triple, and its direction has to be the better
    one there (``tree0.root_dir_differs``: 1 where the other direction
    scores higher at the program's own feature and threshold).  Every
    leaf of trees 0 and 1 has to be ``-eta*G/(H+lambda)`` over the rows
    the tree's own splits and directions route there: tree 0's worst
    leaf, and tree 1's leaves in the mean over the rows
    (``reference_missing.leaf_gaps`` says why; tree 1's worst leaf is
    appended to ``worst_of_tree1`` for the record, compared with
    nothing)."""
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
    d0 = int(trees[0]["dir"][0, 0])
    split = t0 < n_bins - 1
    at_split = float(gains[d0, f0, t0]) if split else 0.0
    out["tree0.root_gain_gap"] = (best - at_split) / abs(best)
    out["tree0.reported_gain_gap"] = (
        abs(float(trees[0]["gain"][0, 0]) - at_split) / abs(best))
    out["tree0.root_dir_differs"] = float(
        split and gains[1 - d0, f0, t0] > gains[d0, f0, t0])

    margin = np.full(len(y), base)
    for k in (0, 1):
        t = trees[k]
        g, h = ref.logistic_grad_hess(margin, y)
        node = ref.descend_binned(bins_t, t, _miss_bin(cfg))
        leaf = ref.leaf_values(node, g, h, n_leaf, eta, lam)
        worst, by_rows = ref.leaf_gaps(
            t["leaf"], leaf, np.bincount(node, minlength=n_leaf))
        if k == 0:
            out["tree0.leaf_gap"] = worst
        else:
            out["tree1.leaf_gap_by_rows"] = by_rows
            if worst_of_tree1 is not None:
                worst_of_tree1.append(worst)
        margin = margin + leaf[node]
    return out


def control_trees(bins_t: np.ndarray, y: np.ndarray,
                  trees: Sequence[Dict[str, np.ndarray]],
                  cfg: Dict[str, Any], control: str = "bfloat16"
                  ) -> List[Dict[str, np.ndarray]]:
    """A control in the program's place: the same two trees as

    * ``bfloat16`` / ``float8``: ``checks.control_trees``' — leaves (and
      tree 0's root split, direction and reported gain) computed by the
      reference in the precision below;
    * ``force_left``: a program that does not learn the direction — every
      ``dir`` 1, the root split the best among the missing-left
      candidates alone, leaves summed over the rows that routing sends
      there."""
    eta, lam = float(cfg["learning_rate"]), float(cfg["reg_lambda"])
    mcw, n_bins = float(cfg["min_child_weight"]), int(cfg["n_bins"])
    force_left = control == "force_left"
    precision = "float64" if force_left else control
    y = np.asarray(y, np.float64)
    margin = np.full(len(y), float(cfg["base_score"]))
    out = []
    for k in (0, 1):
        t = {key: np.array(v) for key, v in trees[k].items()}
        g, h = ref.logistic_grad_hess(margin, y)
        if force_left:
            t["dir"][:] = 1
        if k == 0:
            G, H = ref.root_histogram(bins_t, g, h, n_bins,
                                      precision=precision)
            gains = ref.split_gains(G, H, lam, mcw)
            if force_left:
                gains[0] = -np.inf
            f0, t0, d0 = ref.best_split(gains)
            t["feat"][0, 0], t["thr"][0, 0], t["dir"][0, 0] = f0, t0, d0
            t["gain"][0, 0] = gains[d0, f0, t0]
        node = ref.descend_binned(bins_t, t, _miss_bin(cfg))
        t["leaf"] = ref.leaf_values(node, g, h, len(t["leaf"]), eta, lam,
                                    precision=precision)
        margin = margin + np.asarray(t["leaf"], np.float64)[node]
        out.append(t)
    return out


def learning_numbers(X: np.ndarray, y: np.ndarray, Xh: np.ndarray,
                     yh: np.ndarray, cuts: np.ndarray,
                     trees: Sequence[Dict[str, np.ndarray]],
                     cfg: Dict[str, Any], force_left: bool = False
                     ) -> Dict[str, float]:
    """Does the ensemble learn: logloss on a slice of the training rows
    and AUC on held-out rows, both by the reference's own descent (NaN by
    each node's direction)."""
    base = float(cfg["base_score"])
    return {
        "train_logloss": ref.logloss(
            ref.ensemble_margin(X, cuts, trees, base,
                                force_left=force_left), y),
        "heldout_auc": ref.auc(
            ref.ensemble_margin(Xh, cuts, trees, base,
                                force_left=force_left), yh),
    }
