"""The numbers that decide ``correct`` in the ranking cells: what the timed
path produced, held against ``reference_rank.py`` (the gradient) and
``reference.py`` (the trees).  Pure functions of host arrays, as
``checks.py``'s are, so the self-tests can put each control in the
program's place and see a number leave its limit.  The limits are data,
in the mix files (``checks.apply_limits``).

Every function takes the rows in QUERY ORDER (``reference_rank.
query_bounds``), which is the order of the handle's binned matrix.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from benchmark import reference as ref
from benchmark import reference_rank as rr

#: the controls of ``control_trees``: the gradient's (``reference_rank.
#: CONTROLS``) and the two precisions below the stated ones
CONTROLS = rr.CONTROLS + ("bfloat16", "float8")


def ladder_width(n_docs: int) -> int:
    """The padded width the ``pads_first`` control ranks a query inside: a
    ladder of half octaves from 8 (8, 16, 24, 32, 48, 64, 96, 128, 192,
    ...).  The control's own, not read from the program."""
    w = 8
    while True:
        for cand in (w, w + w // 2):
            if cand >= n_docs and cand % 8 == 0:
                return cand
        w *= 2


def _weight_of(cfg: Dict[str, Any]) -> str:
    return {"rank:ndcg": "ndcg", "rank:pairwise": "pairwise"}[
        cfg["objective"]]


def _gradients(margin, rel, bounds, cfg, control: Optional[str] = None):
    grad_control = control if control in rr.CONTROLS else None
    return rr.lambda_grad_hess(margin, rel, bounds, _weight_of(cfg),
                               grad_control, ladder_width)


def _margin_after(margin: np.ndarray, tree, node: np.ndarray) -> np.ndarray:
    """The margins the PROGRAM holds after a tree: its own float32 leaf
    values added in float32, so that the next round's ranks — ties
    included — are the ones it saw."""
    return (margin.astype(np.float32)
            + np.asarray(tree["leaf"], np.float32)[node])


def boost_tree_numbers(bins_t: np.ndarray, rel: np.ndarray,
                       bounds: np.ndarray,
                       trees: Sequence[Dict[str, np.ndarray]],
                       cfg: Dict[str, Any],
                       round0: Optional[Dict[str, Any]] = None
                       ) -> Dict[str, float]:
    """Trees 0 and 1 of one fit against the reference, on ALL the rows:
    ``checks.boost_tree_numbers`` with LambdaMART's gradient.

    Tree 0 is grown at the all-ties round (every margin is
    ``base_score``: the ranks are the rule for ties alone): the root
    histogram is rebuilt in float64 from the reference's gradients; the
    program's root split has to reach the reference's best gain, the gain
    it reports has to be the reference's for that split, and every leaf
    has to be ``-eta*G/(H+lambda)`` over the rows its own tree routes
    there.  Tree 1 repeats the leaf comparison at the margins after tree
    0, where the scores differ by leaf and tie inside one.

    ``round0`` keeps what the all-ties round gives whatever the trees are
    (the gradients and the root's gains) between calls on the same rows:
    ``tests/rank_on_chip.py`` asks once for the program and once for
    every control."""
    eta, lam = float(cfg["learning_rate"]), float(cfg["reg_lambda"])
    mcw, n_bins = float(cfg["min_child_weight"]), int(cfg["n_bins"])
    n_leaf = len(trees[0]["leaf"])
    out: Dict[str, float] = {}
    margin = np.full(bins_t.shape[1], float(cfg["base_score"]), np.float32)
    for k in (0, 1):
        t = trees[k]
        if k == 0 and round0:
            g, h, gains = round0["g"], round0["h"], round0["gains"]
        else:
            g, h = _gradients(margin, rel, bounds, cfg)
        if k == 0:
            if not round0:
                G, H = ref.root_histogram(bins_t, g, h, n_bins)
                gains = ref.split_gains(G, H, lam, mcw)
                if round0 is not None:
                    round0.update(g=g, h=h, gains=gains)
            best = float(gains.max())
            f0, t0 = int(t["feat"][0, 0]), int(t["thr"][0, 0])
            at_split = float(gains[f0, t0]) if t0 < n_bins - 1 else 0.0
            out["tree0.root_gain_gap"] = (best - at_split) / abs(best)
            out["tree0.reported_gain_gap"] = (
                abs(float(t["gain"][0, 0]) - at_split) / abs(best))
        node = ref.descend_binned(bins_t, t["feat"], t["thr"])
        leaf = ref.leaf_values(node, g, h, n_leaf, eta, lam)
        out[f"tree{k}.leaf_gap"] = ref.worst_leaf_gap(t["leaf"], leaf)
        margin = _margin_after(margin, t, node)
    return out


def control_trees(bins_t: np.ndarray, rel: np.ndarray, bounds: np.ndarray,
                  trees: Sequence[Dict[str, np.ndarray]],
                  cfg: Dict[str, Any], control: str
                  ) -> List[Dict[str, np.ndarray]]:
    """A control in the program's place: the same two trees with their
    leaves (and tree 0's root split and reported gain) computed by the
    reference under a fault — ``reference_rank.CONTROLS`` put one in the
    gradient (queries cut to 128 documents, the |dNDCG| weight dropped,
    ties by reverse position, pads ranked first, bfloat16 pair sums);
    ``bfloat16`` / ``float8`` are ``checks.control_trees``': the sums, or
    the gradients on their way into the kernels, one precision lower."""
    eta, lam = float(cfg["learning_rate"]), float(cfg["reg_lambda"])
    mcw, n_bins = float(cfg["min_child_weight"]), int(cfg["n_bins"])
    precision = control if control in ("bfloat16", "float8") else "float64"
    margin = np.full(bins_t.shape[1], float(cfg["base_score"]), np.float32)
    out = []
    for k in (0, 1):
        t = {key: np.array(v) for key, v in trees[k].items()}
        g, h = _gradients(margin, rel, bounds, cfg, control)
        if k == 0:
            G, H = ref.root_histogram(bins_t, g, h, n_bins,
                                      precision=precision)
            gains = ref.split_gains(G, H, lam, mcw)
            f0, t0 = np.unravel_index(int(np.argmax(gains)), gains.shape)
            t["feat"][0, 0], t["thr"][0, 0] = f0, t0
            t["gain"][0, 0] = gains[f0, t0]
        node = ref.descend_binned(bins_t, t["feat"], t["thr"])
        t["leaf"] = ref.leaf_values(node, g, h, len(t["leaf"]), eta, lam,
                                    precision=precision).astype(np.float32)
        margin = _margin_after(margin, t, node)
        out.append(t)
    return out


def learning_numbers(X: np.ndarray, rel: np.ndarray, bounds: np.ndarray,
                     Xh: np.ndarray, relh: np.ndarray, boundsh: np.ndarray,
                     cuts: np.ndarray,
                     trees: Sequence[Dict[str, np.ndarray]],
                     cfg: Dict[str, Any]) -> Dict[str, float]:
    """Does the ensemble learn to rank: NDCG@10 over some training queries
    and over held-out queries (rows in query order), both by the
    reference's own descent of the raw rows."""
    base = float(cfg["base_score"])
    return {
        "train_ndcg10": rr.ndcg_at(
            ref.ensemble_margin(X, cuts, trees, base), rel, bounds),
        "heldout_ndcg10": rr.ndcg_at(
            ref.ensemble_margin(Xh, cuts, trees, base), relh, boundsh),
    }
