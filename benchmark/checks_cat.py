"""The numbers that decide ``correct`` for a model with CATEGORICAL
columns: what the timed fit produced, held against the plain reference's
replay of it over all the rows (``reference_cat.py``, float64).  Pure
functions of host arrays, so the self-tests and ``tests/cat_on_chip.py``
can put a control in the program's place — the same rows fitted with every
column numeric (codes read as an order), a set shifted by one bin, sums in
bfloat16, gradients in float8 — and see a number leave its limit.  The
limits are data, in the mix's file, with the readings they were set from.

Every gap between gains is measured against the node's terms
``G_L^2/(H_L+l) + G_R^2/(H_R+l)`` of its best split — what a float32 sum's
rounding is a fraction of (``checks_lossguide.py`` says why).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmark import reference as ref
from benchmark import reference_cat as rc
from benchmark import reference_missing


def base_classes(y: np.ndarray, cfg: Dict[str, Any]):
    """Before the first tree a row's gradients are its label's: the
    ``classes`` of ``reference_cat.replay``."""
    base = float(cfg["base_score"])
    g_of, h_of = ref.logistic_grad_hess(np.array([base, base]),
                                        np.array([0.0, 1.0]))
    return (np.asarray(y) > 0.5).astype(np.int64), g_of, h_of


def tree0_numbers(rep: Dict[str, Any], tree: Dict[str, np.ndarray],
                  cfg: Dict[str, Any]) -> Dict[str, float]:
    """The first tree against its replay ``rep``."""
    eta, lam = float(cfg["learning_rate"]), float(cfg["reg_lambda"])
    best = np.maximum(rep["best_gain"], 0.0)
    term = 2.0 * best + rep["G"] ** 2 / (rep["H"] + lam)
    at = np.where(np.isfinite(rep["split_gain"]), rep["split_gain"], -np.inf)
    told = np.asarray(tree["gain"], np.float64)[rep["level"], rep["index"]]
    cat = rep["cat"]
    over = cat & ((rep["set_size"] > int(cfg["max_cat_threshold"]))
                  | rep["set_whole"])
    return {
        # every recorded split attains its node's best gain over ALL
        # features under the rule, and reports it
        "tree0.best_gain_gap": float(np.max((rep["best_gain"] - at) / term)),
        "tree0.reported_gain_gap": float(np.max(np.abs(told - at) / term)),
        # no set larger than max_cat_threshold, none empty or whole
        "tree0.set_over": int(over.sum()),
        "tree0.min_child_hessian": float(rep["child_h"].min()),
        "tree0.leaf_gap": ref.worst_leaf_gap(
            tree["leaf"], -eta * rep["leaf_G"] / (rep["leaf_H"] + lam)),
    }


def tree_numbers(bins_t: np.ndarray, y: np.ndarray,
                 trees: Sequence[Dict[str, np.ndarray]], cuts: np.ndarray,
                 cfg: Dict[str, Any], rule=None
                 ) -> Tuple[Dict[str, float], Dict[str, float]]:
    """Trees 0 and 1 of one fit on ALL the rows: ``(numbers, facts)``.

    Tree 0 (margins are ``base_score``: two gradients, which every format
    holds) is replayed whole, level by level.  Tree 1 repeats the leaf
    comparison on gradients that no short format holds exactly, as the
    mean over the ROWS (``reference_missing.leaf_gaps``).  ``facts`` are
    what the replay counted: the share of the first tree's splits that
    are on categorical columns, its largest set."""
    eta, lam = float(cfg["learning_rate"]), float(cfg["reg_lambda"])
    n_bins = int(cfg["n_bins"])
    used = rc.used_bins(cuts, cfg["feature_types"])
    y = np.asarray(y, np.float64)
    margin = np.full(len(y), float(cfg["base_score"]))
    g, h = ref.logistic_grad_hess(margin, y)
    rep = rc.replay(bins_t, g, h, trees[0], used, cfg,
                    classes=base_classes(y, cfg), rule=rule)
    out = tree0_numbers(rep, trees[0], cfg)
    margin = margin + np.asarray(trees[0]["leaf"],
                                 np.float64)[rep["leaf_of_row"]]
    g, h = ref.logistic_grad_hess(margin, y)
    at = rc.descend_binned(bins_t, trees[1], n_bins)
    n_leaf = len(trees[1]["leaf"])
    want = ref.leaf_values(at, g, h, n_leaf, eta, lam)
    worst, out["tree1.leaf_gap_by_rows"] = reference_missing.leaf_gaps(
        trees[1]["leaf"], want, np.bincount(at, minlength=n_leaf))
    facts = {"splits": int(len(rep["cat"])),
             "cat_split_share": float(rep["cat"].mean()),
             "largest_set": int(rep["set_size"][rep["cat"]].max(initial=0)),
             "tree1.worst_leaf_gap": worst}
    return out, facts


def control_trees(bins_t: np.ndarray, y: np.ndarray,
                  trees: Sequence[Dict[str, np.ndarray]], cuts: np.ndarray,
                  cfg: Dict[str, Any], precision: str = "bfloat16"
                  ) -> List[Dict[str, np.ndarray]]:
    """The control in the program's place: the same two trees with every
    number a sum decides recomputed by the reference in a lower
    ``precision`` — tree 0's recorded gains and its leaves (the splits
    stay the program's), tree 1's leaves.  ``bfloat16``: gradients rounded
    and sums kept in a bfloat16 accumulator; ``float8``: gradients rounded
    to e4m3 and summed exactly (tree 0's gradients are exact in it: tree
    1 judges)."""
    eta, lam = float(cfg["learning_rate"]), float(cfg["reg_lambda"])
    n_bins = int(cfg["n_bins"])
    used = rc.used_bins(cuts, cfg["feature_types"])
    y = np.asarray(y, np.float64)
    margin = np.full(len(y), float(cfg["base_score"]))
    out = []
    for k in (0, 1):
        t = {key: np.array(v) for key, v in trees[k].items()}
        g, h = ref.logistic_grad_hess(margin, y)
        if k == 0:
            rep = rc.replay(bins_t, g, h, t, used, cfg, precision)
            t["gain"][rep["level"], rep["index"]] = rep["split_gain"]
            t["leaf"] = -eta * rep["leaf_G"] / (rep["leaf_H"] + lam)
            at = rep["leaf_of_row"]
        else:
            at = rc.descend_binned(bins_t, t, n_bins)
            t["leaf"] = ref.leaf_values(at, g, h, len(t["leaf"]), eta, lam,
                                        precision=precision)
        margin = margin + np.asarray(t["leaf"], np.float64)[at]
        out.append(t)
    return out


def shifted_sets(tree: Dict[str, np.ndarray], cuts: np.ndarray,
                 cfg: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """The same tree with every categorical split's set moved one bin up
    (bin ``k``'s membership given to bin ``k + 1`` of the feature's bins,
    the last one's to the first): the sets a lookup that is off by one
    would apply."""
    n_bins = int(cfg["n_bins"])
    used = rc.used_bins(cuts, cfg["feature_types"])
    out = {k: np.array(v) for k, v in tree.items()}
    member = rc.set_members(out["cats"], n_bins)
    for level in range(out["feat"].shape[0]):
        for i in range(1 << level):
            c = int(used[out["feat"][level, i]])
            if c and out["thr"][level, i] < n_bins - 1:
                member[level, i, :c] = np.roll(member[level, i, :c], 1)
    out["cats"] = rc.set_words(member)
    return out


def as_sets(tree: Dict[str, np.ndarray], n_bins: int) -> Dict[str, np.ndarray]:
    """A tree of thresholds alone (a model without categorical columns)
    in the form the replay reads: every node's left set its bins ``<=
    thr``."""
    out = {k: np.asarray(v) for k, v in tree.items()}
    out["cats"] = rc.set_words(
        np.arange(n_bins) <= np.asarray(tree["thr"])[..., None])
    return out


def tables_mismatches(X_cols: Dict[int, np.ndarray], cuts: np.ndarray,
                      n_bins: int) -> int:
    """Entries of the program's category→bin tables that are not the
    reference's, made from ALL the rows' codes (``X_cols``: column ->
    its codes)."""
    return int(sum(np.count_nonzero(rc.cat_table(col, n_bins) != cuts[f])
                   for f, col in X_cols.items()))


def bins_mismatches(X_rows: np.ndarray, bins_rows_t: np.ndarray,
                    cuts: np.ndarray, feature_types: Sequence[str]) -> int:
    """Entries of a block of the binned matrix (feature-major ``[F, k]``)
    that are not ``reference_cat.bin_rows`` of the raw rows against the
    program's own tables and cuts."""
    want = rc.bin_rows(X_rows, cuts, feature_types)
    return int(np.count_nonzero(want.T != bins_rows_t))


def learning_numbers(X: np.ndarray, y: np.ndarray, yh: np.ndarray,
                     heldout_scores: np.ndarray, cuts: np.ndarray,
                     trees: Sequence[Dict[str, np.ndarray]],
                     cfg: Dict[str, Any]) -> Dict[str, float]:
    """Does the ensemble learn: logloss on a slice of the training rows
    by the reference's own binning and descent, AUC on held-out rows of
    what the program's ``predict`` answered (``heldout_scores``), so that
    the scoring path's tables and membership are judged too."""
    margin = rc.ensemble_margin(X, cuts, cfg["feature_types"], trees,
                                float(cfg["base_score"]), int(cfg["n_bins"]))
    return {"train_logloss": ref.logloss(margin, y),
            "heldout_auc": ref.auc(heldout_scores, yh)}
