"""The numbers that decide ``correct`` for LEAF-WISE trees: what the timed
fit produced, held against the plain reference's replay of it over all the
rows (``reference_lossguide.py``, float64).  Pure functions of host
arrays, so the self-tests and ``tests/lossguide_on_chip.py`` can put a
control in the program's place — the tree with its sums recomputed in
bfloat16, or from float8 gradients; a swapped expansion order; a
depth-wise tree under the same leaf count — and see a number leave its
limit.  The limits are data, in the mix's file, with the readings they
were set from.

Scales.  A split's gain is a difference of terms ``G^2 / (H + lambda)``
that are as large as the node is heavy, so float32 sums carry an error in
proportion to the node's MASS, not to its gain: a late expansion's gain of
a few tens sits on terms of a few hundred thousand.  Every gap between
gains is therefore measured against ``term(i) = G_L^2/(H_L+l) +
G_R^2/(H_R+l)`` of the node's best split — what the float32 rounding is a
fraction of — and the limits are fractions of it.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import numpy as np

from benchmark import reference as ref
from benchmark import reference_lossguide as rl
from benchmark import reference_missing


def _terms(G: float, H: float, gain: float, lam: float) -> float:
    """``G_L^2/(H_L+l) + G_R^2/(H_R+l)`` of a split of gain ``gain`` at a
    node of sums ``(G, H)``: twice the gain plus the parent's term."""
    return 2.0 * gain + G * G / (H + lam)


def tree0_numbers(rep: Dict[str, Any], tree: Dict[str, np.ndarray],
                  cfg: Dict[str, Any]) -> Dict[str, float]:
    """The first tree against its replay ``rep``
    (``reference_lossguide.replay``)."""
    eta, lam = float(cfg["learning_rate"]), float(cfg["reg_lambda"])
    gamma = float(cfg.get("gamma", 0.0))
    left, right = np.asarray(tree["left"]), np.asarray(tree["right"])
    order, leaves = rep["order"], rep["leaves"]
    best = np.where(np.isfinite(rep["best_gain"]), rep["best_gain"], -np.inf)
    term = np.array([_terms(G, H, max(b, 0.0), lam)
                     for G, H, b in zip(rep["G"], rep["H"], best)])
    out: Dict[str, float] = {}
    # exactly the budget's leaves
    out["tree0.leaves_off"] = abs(int(cfg["max_leaves"]) - len(leaves))
    # every recorded split attains its node's best gain, and reports it
    at = rep["split_gain"][order]
    out["tree0.best_gain_gap"] = float(np.max(
        (best[order] - np.where(np.isfinite(at), at, -np.inf))
        / term[order]))
    out["tree0.reported_gain_gap"] = float(np.max(
        np.abs(np.asarray(tree["gain"], np.float64)[order] - at)
        / term[order]))
    # the budget's rule, step by step: expansion k took the open leaf of
    # highest gain.  The node ids say the order (expansion k made 2k+1 and
    # 2k+2); a leaf whose gain beats the chosen node's by less than the
    # float32 rounding of either's terms may go first
    worst = 0.0
    is_open = np.zeros(len(left), bool)
    is_open[0] = True
    for i in order:
        cand = np.flatnonzero(is_open)
        j = cand[np.argmax(best[cand])]
        worst = max(worst, (best[j] - best[i]) / max(term[i], term[j]))
        is_open[i] = False
        is_open[[left[i], right[i]]] = True
    # ... and a tree that stopped short left no leaf worth splitting
    if len(leaves) < int(cfg["max_leaves"]):
        cand = np.flatnonzero(is_open)
        j = cand[np.argmax(best[cand])]
        if best[j] > gamma:
            worst = max(worst, (best[j] - gamma) / term[j])
    out["tree0.order_gap"] = float(worst)
    # children hold min_child_weight of hessian; leaves are -eta G/(H+l)
    kids = np.concatenate([left[order], right[order]])
    out["tree0.min_child_hessian"] = float(rep["H"][kids].min())
    out["tree0.leaf_gap"] = ref.worst_leaf_gap(
        np.asarray(tree["value"])[leaves],
        -eta * rep["G"][leaves] / (rep["H"][leaves] + lam))
    return out


def tree_numbers(bins_t: np.ndarray, y: np.ndarray,
                 trees: Sequence[Dict[str, np.ndarray]], cfg: Dict[str, Any]
                 ) -> Tuple[Dict[str, float], Dict[str, float]]:
    """Trees 0 and 1 of one fit on ALL the rows: ``(numbers, facts)``.

    Tree 0 (margins are ``base_score``: gradients +-0.5 and 0.25, which
    every format holds) is replayed whole.  Tree 1 repeats the leaf
    comparison on gradients that no short format holds exactly — the
    configuration states bfloat16 for the gradients on their way into the
    histogram kernels — as the mean over the ROWS
    (``reference_missing.leaf_gaps``: a leaf's float32 error is absolute,
    so it falls with its rows, and 255 leaves down to
    ``min_child_weight`` have small ones among them; the worst leaf's gap
    is printed with the facts and compared with nothing).  ``facts`` are
    what the replay counted, for the readers of a traced run."""
    eta, lam = float(cfg["learning_rate"]), float(cfg["reg_lambda"])
    mcw, n_bins = float(cfg["min_child_weight"]), int(cfg["n_bins"])
    y = np.asarray(y, np.float64)
    margin = np.full(len(y), float(cfg["base_score"]))
    g, h = ref.logistic_grad_hess(margin, y)
    rep = rl.replay(bins_t, g, h, trees[0], n_bins, lam, mcw)
    out = tree0_numbers(rep, trees[0], cfg)
    margin = margin + np.asarray(trees[0]["value"],
                                 np.float64)[rep["leaf_of_row"]]
    g, h = ref.logistic_grad_hess(margin, y)
    leaves, want, _, at = rl.leaf_values(bins_t, g, h, trees[1], eta, lam)
    rows = np.bincount(np.searchsorted(leaves, at), minlength=len(leaves))
    worst, out["tree1.leaf_gap_by_rows"] = reference_missing.leaf_gaps(
        np.asarray(trees[1]["value"])[leaves], want, rows)
    facts = {"needed_rows": rl.needed_rows(rep, trees[0]),
             "rows": int(rep["rows"][0]), "builds": len(rep["order"]) + 1,
             "depth": rl.depth_of(trees[0]), "leaves": len(rep["leaves"]),
             "tree1.worst_leaf_gap": worst}
    return out, facts


def control_trees(bins_t: np.ndarray, y: np.ndarray,
                  trees: Sequence[Dict[str, np.ndarray]], cfg: Dict[str, Any],
                  precision: str = "bfloat16"):
    """The control in the program's place: the same two trees with every
    number a sum decides recomputed by the reference in a lower
    ``precision`` — tree 0's recorded gains and its leaves from histograms
    summed that way (the splits and the order stay the program's), tree 1's
    leaves likewise.  ``bfloat16``: gradients rounded and sums kept in a
    bfloat16 accumulator, the step below the float32 sums the configuration
    states; ``float8``: gradients rounded to e4m3 and summed exactly, the
    step below the bfloat16 it states for the gradients on their way into
    the kernels (tree 0's gradients are exact in it: tree 1 judges)."""
    eta, lam = float(cfg["learning_rate"]), float(cfg["reg_lambda"])
    mcw, n_bins = float(cfg["min_child_weight"]), int(cfg["n_bins"])
    y = np.asarray(y, np.float64)
    margin = np.full(len(y), float(cfg["base_score"]))
    out = []
    for k in (0, 1):
        t = {key: np.array(v) for key, v in trees[k].items()}
        g, h = ref.logistic_grad_hess(margin, y)
        if k == 0:
            rep = rl.replay(bins_t, g, h, t, n_bins, lam, mcw, precision)
            order, leaves = rep["order"], rep["leaves"]
            t["gain"][order] = rep["split_gain"][order]
            t["value"][leaves] = (-eta * rep["G"][leaves]
                                  / (rep["H"][leaves] + lam))
            at = rep["leaf_of_row"]
        else:
            leaves, values, _, at = rl.leaf_values(bins_t, g, h, t, eta, lam,
                                                   precision)
            t["value"][leaves] = values
        margin = margin + np.asarray(t["value"], np.float64)[at]
        out.append(t)
    return out


def swapped_order(tree: Dict[str, np.ndarray], k: int = -1
                  ) -> Dict[str, np.ndarray]:
    """The same tree with expansions ``k`` and ``k + 1`` made in each
    other's place: the nodes they created trade ids.  ``k`` has to be such
    that expansion ``k + 1`` did not split a child of expansion ``k``
    (-1: the first such pair)."""
    order = rl.expansion_order(tree)
    free = [j for j in range(len(order) - 1)
            if order[j + 1] not in (2 * j + 1, 2 * j + 2)]
    k = free[0] if k < 0 else k
    if k not in free:
        raise ValueError(f"expansion {k + 1} split a child of expansion {k}")
    perm = np.arange(len(tree["left"]))
    for x, y in ((2 * k + 1, 2 * k + 3), (2 * k + 2, 2 * k + 4)):
        perm[x], perm[y] = y, x
    out = {key: np.array(v)[perm] for key, v in tree.items()}
    for key in ("left", "right"):
        kids = out[key]
        out[key] = np.where(kids > 0, perm[np.maximum(kids, 0)], kids)
    return out


def learning_numbers(X: np.ndarray, y: np.ndarray, Xh: np.ndarray,
                     yh: np.ndarray, cuts: np.ndarray,
                     trees: Sequence[Dict[str, np.ndarray]],
                     cfg: Dict[str, Any]) -> Dict[str, float]:
    """Does the ensemble learn: logloss on a slice of the training rows
    and AUC on held-out rows, both by the reference's own descent of the
    node lists."""
    base = float(cfg["base_score"])
    return {
        "train_logloss": ref.logloss(
            rl.ensemble_margin(X, cuts, trees, base), y),
        "heldout_auc": ref.auc(
            rl.ensemble_margin(Xh, cuts, trees, base), yh),
    }
