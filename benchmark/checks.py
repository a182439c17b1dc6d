"""The numbers that decide ``correct``: what the timed path produced, held
against the plain reference (``reference.py``).  Pure functions of host
arrays, so the self-tests can put the control — the reference computed in
bfloat16 — in the program's place and see each number leave its limit.

Every function returns ``{name: value}``; the limits are data, in the
traffic mix's file under ``limits``, beside ``passes`` (``at_most`` unless
the mix says ``at_least``).  PERF.md section 2 gives the readings each
limit was set from.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

from benchmark import reference as ref


def apply_limits(ctx, numbers: Dict[str, float]) -> None:
    """Record each number beside its limit from the mix file.  A number
    without a limit is a fault of the mix file, not a pass."""
    limits = ctx.mix["limits"]
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"the mix gives no limit for {name!r}")
        lim = limits[name]
        if isinstance(lim, dict):
            ctx.compare(name, value, lim["limit"], lim.get("passes", "at_most"))
        else:
            ctx.compare(name, value, lim)


# -- boost -----------------------------------------------------------------------

def boost_tree_numbers(bins_t: np.ndarray, y: np.ndarray,
                       trees: Sequence[Dict[str, np.ndarray]],
                       cfg: Dict[str, Any]) -> Dict[str, float]:
    """Trees 0 and 1 of one fit against the reference, on ALL the rows.

    Tree 0 (margins are ``base_score``, so gradients are known): the root
    histogram is rebuilt from integer counts; the program's root split
    has to reach the reference's best gain (a tie may pick another
    feature, a wrong split may not), the gain it reports for that split
    has to be the reference's, and every leaf has to be
    ``-eta*G/(H+lambda)`` over the rows its own tree routes there.
    Tree 1 repeats the leaf comparison on gradients that no short format
    holds exactly — tree 0's are +-0.5 and 0.25, which every format holds,
    so tree 0 alone says nothing about the gradients' precision.  The
    configurations state bfloat16 for the gradients on their way into the
    histogram kernels, so this gap is a few 1e-3 in sound runs and its
    limit is set between that and what float8 gradients give.
    """
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
        out[f"tree{k}.leaf_gap"] = ref.worst_leaf_gap(t["leaf"], leaf)
        margin = margin + leaf[node]
    return out


def control_trees(bins_t: np.ndarray, y: np.ndarray,
                  trees: Sequence[Dict[str, np.ndarray]],
                  cfg: Dict[str, Any], precision: str = "bfloat16"
                  ) -> List[Dict[str, np.ndarray]]:
    """The control in the program's place: the same two trees with their
    leaves (and tree 0's root split and reported gain) computed by the
    reference in a lower precision — ``bfloat16``: gradients rounded and
    sums kept in a bfloat16 accumulator, the step below the float32 sums
    the configurations state; ``float8``: gradients rounded to e4m3 and
    summed exactly, the step below the bfloat16 they state for the
    gradients on their way into the histogram kernels."""
    eta, lam = float(cfg["learning_rate"]), float(cfg["reg_lambda"])
    mcw, n_bins = float(cfg["min_child_weight"]), int(cfg["n_bins"])
    y = np.asarray(y, np.float64)
    margin = np.full(len(y), float(cfg["base_score"]))
    out = []
    for k in (0, 1):
        t = {key: np.array(v) for key, v in trees[k].items()}
        g, h = ref.logistic_grad_hess(margin, y)
        node = ref.descend_binned(bins_t, t["feat"], t["thr"])
        t["leaf"] = ref.leaf_values(node, g, h, len(t["leaf"]), eta, lam,
                                    precision=precision)
        if k == 0:
            G, H = ref.root_histogram(bins_t, g, h, n_bins,
                                      precision=precision)
            gains = ref.split_gains(G, H, lam, mcw)
            f0, t0 = np.unravel_index(int(np.argmax(gains)), gains.shape)
            t["feat"][0, 0], t["thr"][0, 0] = f0, t0
            t["gain"][0, 0] = gains[f0, t0]
        margin = margin + np.asarray(t["leaf"], np.float64)[node]
        out.append(t)
    return out


def trees_differ(a: Sequence[Dict[str, np.ndarray]],
                 b: Sequence[Dict[str, np.ndarray]]) -> int:
    """How many arrays of two ensembles are not byte-identical."""
    if len(a) != len(b):
        return abs(len(a) - len(b)) + 1
    return sum(int(not np.array_equal(ta[k], tb[k]))
               for ta, tb in zip(a, b) for k in ta)


def learning_numbers(X: np.ndarray, y: np.ndarray, Xh: np.ndarray,
                     yh: np.ndarray, cuts: np.ndarray,
                     trees: Sequence[Dict[str, np.ndarray]],
                     cfg: Dict[str, Any]) -> Dict[str, float]:
    """Does the ensemble learn: logloss on a slice of the training rows
    and AUC on held-out rows, both by the reference's own descent."""
    base = float(cfg["base_score"])
    return {
        "train_logloss": ref.logloss(
            ref.ensemble_margin(X, cuts, trees, base), y),
        "heldout_auc": ref.auc(
            ref.ensemble_margin(Xh, cuts, trees, base), yh),
    }


# -- ingest ----------------------------------------------------------------------

def cuts_gap(X: np.ndarray, cuts: np.ndarray, features: Sequence[int],
             cfg: Dict[str, Any]) -> float:
    """Widest gap between the program's cut points of a few features and
    the reference's, against ``max(|cut|, 1)``."""
    n_bins = int(cfg["n_bins"])
    worst = 0.0
    for f in features:
        want = ref.quantile_cuts(X[:, f], n_bins, int(cfg["n_summary"]))
        gap = np.abs(np.asarray(cuts[f], np.float64) - want)
        worst = max(worst, float(np.max(gap / np.maximum(np.abs(want), 1.0))))
    return worst


def bins_mismatches(X_rows: np.ndarray, bins_rows_t: np.ndarray,
                    cuts: np.ndarray) -> int:
    """Entries of a block of the binned matrix (feature-major ``[F, k]``)
    that are not the number of the program's own cuts ``<= x``."""
    want = ref.bin_rows(X_rows, cuts)
    return int(np.count_nonzero(want.T != bins_rows_t))


# -- score -----------------------------------------------------------------------

def score_gap(slabs: Sequence[np.ndarray], outputs: Sequence[np.ndarray],
              cuts: np.ndarray, trees: Sequence[Dict[str, np.ndarray]],
              cfg: Dict[str, Any], precision: str = "float64") -> float:
    """Widest gap between the probabilities the scoring calls returned and
    the sigmoid of a plain descent of the model's own trees."""
    worst = 0.0
    for X, got in zip(slabs, outputs):
        want = ref.sigmoid(ref.ensemble_margin(
            X, cuts, trees, float(cfg["base_score"]), precision))
        worst = max(worst, float(np.max(np.abs(
            np.asarray(got, np.float64) - want))))
    return worst
