"""The numbers that decide ``correct`` for a fit of several classes: what
the timed path produced, held against the plain reference
(``reference_multi.py`` for the softmax, ``reference.py`` for histogram,
gain, leaf and descent).  Pure functions of host arrays, on ALL the rows
(the descents run in row chunks), so the self-tests and
``tests/multi_on_chip.py`` can put a control in the program's place and
see a number leave its limit.

``rounds`` is the model's own list: one dict a ROUND, its arrays with the
class first (``feat`` ``[K, depth, half]``, ``leaf`` ``[K, 2**depth]``).
The limits are data, in the traffic mix's file under ``limits``; PERF.md
section 2 ("At the multiclass shape") gives the readings each was set
from.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from benchmark import reference as ref
from benchmark import reference_missing
from benchmark import reference_multi as rm

#: what :func:`control_trees` can put in the program's place
CONTROLS = rm.GRAD_CONTROLS + ("bfloat16", "float8", "shifted")


def _params(cfg: Dict[str, Any]):
    return (float(cfg["learning_rate"]), float(cfg["reg_lambda"]),
            float(cfg["min_child_weight"]), int(cfg["n_bins"]),
            float(cfg["base_score"]))


def _round0_histograms(counts: np.ndarray, g_of: np.ndarray,
                       h_of: np.ndarray, c: int):
    """``[F, n_bins]`` sums of class c's round-0 tree at the root: every
    row of a label has one gradient, so integer counts times K numbers."""
    return (np.einsum("l,flb->fb", g_of[:, c], counts),
            np.einsum("l,flb->fb", h_of[:, c], counts))


def boost_tree_numbers(bins_t: np.ndarray, y: np.ndarray,
                       rounds: Sequence[Dict[str, np.ndarray]],
                       cfg: Dict[str, Any],
                       worst_leaf: Optional[Dict[str, float]] = None
                       ) -> Dict[str, float]:
    """Rounds 0 and 1 of one fit against the reference, on ALL the rows,
    class by class; each number is the WORST over the K trees of a round.

    Round 0 (every margin is ``base_score``, so a row's K gradients are
    known from its label): class c's root histogram from integer counts;
    the program's root split has to reach the reference's best gain, the
    gain it reports has to be the reference's, and every leaf has to be
    ``-eta*G/(H+lambda)`` over the rows its own tree routes there.
    Round 1 repeats the leaf comparison at the margins the round-0 trees
    leave — the program's own trees, descended plainly, class c's onto
    column c — where a row's K gradients come from ONE softmax over its K
    margins: a round whose classes are not coupled (one-vs-rest), or
    whose trees land on another class's column, shows here.

    The leaves are judged in the mean over the ROWS
    (``reference_missing.leaf_gaps``, which has the argument): a class of
    one row in 200 has gradients all but 0.5% of one sign, the softmax's
    first gradients (1/K and 1/K - 1) are no dyadic numbers as a
    sigmoid's +-0.5 are, so a small leaf — a right child several
    subtractions below a root sum of ~1e6 — reads percents off in a SOUND
    run.  ``worst_leaf``, where given, receives the worst leaf of each
    round, which is compared with nothing.
    """
    eta, lam, mcw, n_bins, base = _params(cfg)
    y_i = np.asarray(y, np.int64)
    K, n_leaf = np.asarray(rounds[0]["leaf"]).shape
    out = {"tree0.root_gain_gap": 0.0, "tree0.reported_gain_gap": 0.0,
           "tree0.leaf_gap_by_rows": 0.0, "tree1.leaf_gap_by_rows": 0.0}
    worst_leaf = {} if worst_leaf is None else worst_leaf

    counts = rm.class_bin_counts(bins_t, y_i, K, n_bins)
    g_of, h_of = rm.softmax_grad_hess(np.full((K, K), base), np.arange(K))
    for c in range(K):
        G, H = _round0_histograms(counts, g_of, h_of, c)
        gains = ref.split_gains(G, H, lam, mcw)
        best = float(gains.max())
        t = rm.class_tree(rounds[0], c)
        f0, t0 = int(t["feat"][0, 0]), int(t["thr"][0, 0])
        at_split = float(gains[f0, t0]) if t0 < n_bins - 1 else 0.0
        scale = max(abs(best), 1e-300)
        out["tree0.root_gain_gap"] = max(out["tree0.root_gain_gap"],
                                         (best - at_split) / scale)
        out["tree0.reported_gain_gap"] = max(
            out["tree0.reported_gain_gap"],
            abs(float(t["gain"][0, 0]) - at_split) / scale)
    del counts

    margin = np.full((len(y_i), K), base)
    for k in (0, 1):
        g, h = rm.softmax_grad_hess(margin, y_i)
        for c in range(K):
            t = rm.class_tree(rounds[k], c)
            node = ref.descend_binned(bins_t, t["feat"], t["thr"])
            leaf = ref.leaf_values(node, g[:, c], h[:, c], n_leaf, eta, lam)
            worst, by_rows = reference_missing.leaf_gaps(
                t["leaf"], leaf, np.bincount(node, minlength=n_leaf))
            name = f"tree{k}.leaf_gap"
            worst_leaf[name] = max(worst_leaf.get(name, 0.0), worst)
            out[name + "_by_rows"] = max(out[name + "_by_rows"], by_rows)
            if k == 0:
                margin[:, c] += np.asarray(t["leaf"], np.float64)[node]
    return out


def control_trees(bins_t: np.ndarray, y: np.ndarray,
                  rounds: Sequence[Dict[str, np.ndarray]],
                  cfg: Dict[str, Any], control: str
                  ) -> List[Dict[str, np.ndarray]]:
    """The control in the program's place: the same two rounds of trees
    with their leaves (and round 0's root splits and reported gains)
    computed by the reference with a fault in it — ``ovr``, ``hess1``,
    ``bf16_margin`` (``reference_multi.softmax_grad_hess``'s), ``bfloat16``
    (gradients rounded, histogram and leaf sums kept in a bfloat16
    accumulator: the step below the float32 sums the configuration
    states), ``float8`` (gradients rounded to e4m3 and summed exactly:
    the step below the bfloat16 it states for the gradients on their way
    into the kernels), ``shifted`` (class c's round-0 trees added onto
    column c + 1 of the margins round 1 starts from)."""
    eta, lam, mcw, n_bins, base = _params(cfg)
    y_i = np.asarray(y, np.int64)
    K, n_leaf = np.asarray(rounds[0]["leaf"]).shape
    grad_control = control if control in rm.GRAD_CONTROLS else ""
    precision = control if control in ("bfloat16", "float8") else "float64"
    shift = 1 if control == "shifted" else 0
    margin = np.full((len(y_i), K), base)
    # round 0's root sums: exact from integer counts (a label has one
    # gradient); only a bfloat16 running sum has to walk the rows
    counts = (None if precision == "bfloat16" else
              rm.class_bin_counts(bins_t, y_i, K, n_bins))
    g_of, h_of = (ref._round_inputs(a, precision) for a in
                  rm.softmax_grad_hess(np.full((K, K), base), np.arange(K),
                                       grad_control))
    out = []
    for k in (0, 1):
        g, h = rm.softmax_grad_hess(margin, y_i, grad_control)
        r = {key: np.array(v, np.float64 if key == "leaf" else None)
             for key, v in rounds[k].items()}
        after = margin.copy()
        for c in range(K):
            node = ref.descend_binned(bins_t, r["feat"][c], r["thr"][c])
            r["leaf"][c] = ref.leaf_values(node, g[:, c], h[:, c], n_leaf,
                                           eta, lam, precision=precision)
            if k == 0:
                G, H = (_round0_histograms(counts, g_of, h_of, c)
                        if counts is not None else
                        ref.root_histogram(bins_t, g[:, c], h[:, c], n_bins,
                                           precision=precision))
                gains = ref.split_gains(G, H, lam, mcw)
                f0, t0 = np.unravel_index(int(np.argmax(gains)), gains.shape)
                r["feat"][c, 0, 0], r["thr"][c, 0, 0] = f0, t0
                r["gain"][c, 0, 0] = gains[f0, t0]
            after[:, (c + shift) % K] += r["leaf"][c][node]
        margin = after
        out.append(r)
    return out


def learning_numbers(X: np.ndarray, y: np.ndarray, Xh: np.ndarray,
                     yh: np.ndarray, cuts: np.ndarray,
                     rounds: Sequence[Dict[str, np.ndarray]],
                     cfg: Dict[str, Any]) -> Dict[str, float]:
    """Does the ensemble learn: the multiclass log loss on a slice of the
    training rows and the error rate on held-out rows, both by the
    reference's own descent of the K trees of every round."""
    base = float(cfg["base_score"])
    return {
        "train_mlogloss": rm.mlogloss(
            rm.ensemble_margin(X, cuts, rounds, base), y),
        "heldout_merror": rm.merror(
            rm.ensemble_margin(Xh, cuts, rounds, base), yh),
    }


def score_gap(X: np.ndarray, got: np.ndarray, cuts: np.ndarray,
              rounds: Sequence[Dict[str, np.ndarray]], cfg: Dict[str, Any],
              precision: str = "float64", shift: int = 0) -> float:
    """Widest gap between the ``[n, K]`` margins one ``predict`` returned
    and a plain float64 descent of the model's own trees on raw values.
    ``precision="bfloat16"`` (every leaf and partial sum rounded) and
    ``shift=1`` are the controls."""
    want = rm.ensemble_margin(X, cuts, rounds, float(cfg["base_score"]),
                              precision, shift)
    got = np.asarray(got, np.float64)
    if got.shape != want.shape:
        return float("inf")
    return float(np.max(np.abs(got - want)))
