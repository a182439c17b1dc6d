"""The plain reference of NATIVE CATEGORICAL features: float64 numpy, no
kernels, importing nothing of the program.

The rule, as LightGBM (``Features.rst``, "Optimal Split for Categorical
Features") and XGBoost (``tutorials/categorical.rst``, ``tree_method=hist``)
publish it, and as the configuration's ``guarantees`` state it:

* *bins.*  A categorical column holds whole-number codes.  Its
  category→bin TABLE names the codes by falling count over all rows (ties
  to the lower code): bins ``0, 1, 2, ...``, at most ``n_bins - 1`` of
  them; every rarer level, and every code the table lacks, shares the last
  bin ``n_bins - 1`` ("other").  A table is a row of the cut matrix: the
  code of bin ``k`` at ``k``, -1 past the named bins.  A numeric column
  keeps its cut points (bin = number of cuts ``<= x``).
* *split.*  A categorical feature of ``c`` bins (its named ones; all
  ``n_bins`` where every name is taken) orders them, per node, by ``G_k /
  (H_k + lambda)`` — ascending and stable, so a bin that is empty in the
  node sits at 0 among the others, ties by bin id — and offers the ``c -
  1`` cuts of that order that leave at most ``max_cat_threshold`` bins on
  one side: a prefix of 1..T bins from either end.  The SET is that side
  and goes LEFT.  With ``c <= max_cat_to_onehot`` each single bin against
  the rest instead.  XGBoost's gain, ``min_child_weight`` on both
  children; candidate ``j`` stands where threshold ``j`` of a numeric
  feature does and the best over ``[F x (n_bins - 1)]`` wins, ties to the
  lower flat index.
* *route / predict.*  Left iff the row's bin is in the node's set.

Tree arrays are the model's own format for such a model: ``feat`` /
``thr`` / ``gain`` ``[depth, half]``, ``leaf`` ``[2**depth]`` and ``cats``
``[depth, half, n_bins / 32]`` — EVERY node's left set as 32-bit words (bin
``b`` is bit ``b % 32`` of word ``b // 32``); a numeric split's set is its
bins ``<= thr``, a node without a split holds every bin, and a categorical
node's ``thr`` is its set's size less one.

:func:`grow` builds a tree itself (the tier-1 tests hold the program's
first tree to it); :func:`replay` takes a tree somebody else built and
recomputes, level by level over the tree's own rows, every node's float64
sums, the best split over ALL features under the rule, the recorded
split's gain and the leaves — what ``checks_cat`` holds the timed fit
against.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from benchmark import reference as ref

WORD = 32


# -- tables and bins ---------------------------------------------------------

def is_cat(feature_types: Sequence[str]) -> np.ndarray:
    return np.asarray([t == "c" for t in feature_types], bool)


def cat_table(col: np.ndarray, n_bins: int) -> np.ndarray:
    """The category→bin table of one column of codes: ``[n_bins - 1]``."""
    counts = np.bincount(np.asarray(col).astype(np.int64))
    order = np.argsort(-counts, kind="stable")
    order = order[counts[order] > 0][:n_bins - 1]
    table = np.full(n_bins - 1, -1.0)
    table[:len(order)] = order
    return table


def used_bins(cuts: np.ndarray, feature_types: Sequence[str]) -> np.ndarray:
    """Per feature the bins ``0..c-1`` a categorical column's rows can
    hold (0: numeric): its named bins, all of them where every name is
    taken."""
    named = (np.asarray(cuts) >= 0).sum(axis=1)
    c = np.where(named < np.shape(cuts)[1], named, named + 1)
    return np.where(is_cat(feature_types), c, 0)


def bin_rows(X: np.ndarray, cuts: np.ndarray, feature_types: Sequence[str]
             ) -> np.ndarray:
    """``[n, F]`` bins of raw rows against the cut matrix: a numeric
    column by its cuts, a categorical one by its table."""
    cuts = np.asarray(cuts, np.float64)
    out = np.empty(X.shape, np.int64)
    for f, cat in enumerate(is_cat(feature_types)):
        col = np.asarray(X[:, f], np.float64)
        if not cat:
            out[:, f] = np.searchsorted(cuts[f], col, side="right")
            continue
        # a lookup by code; what is no named code (rarer, unseen,
        # negative, fractional) is "other", the last bin
        named = cuts[f][cuts[f] >= 0].astype(np.int64)
        other = cuts.shape[1]
        lut = np.full(int(named.max(initial=0)) + 2, other, np.int64)
        lut[named] = np.arange(len(named))
        code = np.where((col >= 0) & (col == np.floor(col))
                        & (col < len(lut) - 1), col, len(lut) - 1)
        out[:, f] = lut[code.astype(np.int64)]
    return out


# -- sets --------------------------------------------------------------------

def set_words(member: np.ndarray) -> np.ndarray:
    """``[..., n_bins]`` bools as ``[..., ceil(n_bins / 32)]`` int32 words."""
    pad = -member.shape[-1] % WORD
    bits = np.concatenate(
        [member, np.zeros(member.shape[:-1] + (pad,), bool)], axis=-1
    ).reshape(member.shape[:-1] + (-1, WORD))
    return (bits.astype(np.uint64) << np.arange(WORD, dtype=np.uint64)
            ).sum(axis=-1).astype(np.uint32).view(np.int32)


def set_members(words: np.ndarray, n_bins: int) -> np.ndarray:
    """The inverse: ``[..., W]`` words as ``[..., n_bins]`` bools."""
    u = np.asarray(words).astype(np.int64) & 0xFFFFFFFF
    bits = (u[..., None] >> np.arange(WORD)) & 1
    return bits.reshape(u.shape[:-1] + (-1,))[..., :n_bins].astype(bool)


# -- the split scan ----------------------------------------------------------

def _gain(gl, hl, gt, ht, lam: float, mcw: float):
    gr, hr = gt - gl, ht - hl
    gain = 0.5 * (gl ** 2 / (hl + lam) + gr ** 2 / (hr + lam)
                  - gt ** 2 / (ht + lam))
    return np.where((hl >= mcw) & (hr >= mcw), gain, -np.inf)


def cat_candidates(G: np.ndarray, H: np.ndarray, c: int, lam: float,
                   mcw: float, onehot: int, T: int
                   ) -> Tuple[np.ndarray, List[np.ndarray]]:
    """One categorical feature of one node: ``(gains [n_bins - 1], sets)``,
    candidate ``j``'s gain (``-inf`` where the rule offers none) and the
    bins of its left set."""
    n_bins = len(G)
    gains = np.full(n_bins - 1, -np.inf)
    sets: List[np.ndarray] = [np.zeros(0, np.int64)] * (n_bins - 1)
    if c < 2:
        return gains, sets
    order = np.argsort(G[:c] / (H[:c] + lam), kind="stable")
    gt, ht = G.sum(), H.sum()
    if c <= onehot:
        for j in range(c):
            sets[j] = order[j:j + 1]
    else:
        for j in range(min(c - 1, n_bins - 1)):
            m = j + 1
            if m <= T:
                sets[j] = order[:m]
            elif c - m <= T:
                sets[j] = order[m:]
    for j, s in enumerate(sets):
        if len(s):
            gains[j] = _gain(G[s].sum(), H[s].sum(), gt, ht, lam, mcw)
    return gains, sets


def best_split(G: np.ndarray, H: np.ndarray, used: np.ndarray,
               cfg: Dict[str, Any]) -> Tuple[int, int, float, np.ndarray]:
    """``(feature, candidate, gain, left set [n_bins] bool)`` of one
    node's ``[F, n_bins]`` sums: a loop over the features, each scanned
    by its own rule; the first of the best wins."""
    lam, mcw = float(cfg["reg_lambda"]), float(cfg["min_child_weight"])
    onehot, T = int(cfg["max_cat_to_onehot"]), int(cfg["max_cat_threshold"])
    F, n_bins = G.shape
    gains = np.full((F, n_bins - 1), -np.inf)
    sets: List[Any] = [None] * F
    for f in range(F):
        if used[f]:
            gains[f], sets[f] = cat_candidates(G[f], H[f], int(used[f]), lam,
                                               mcw, onehot, T)
        else:
            gains[f] = ref.split_gains(G[f:f + 1], H[f:f + 1], lam, mcw)[0]
    f, j = np.unravel_index(int(np.argmax(gains)), gains.shape)
    left = np.zeros(n_bins, bool)
    if used[f]:
        left[sets[f][j]] = True
    else:
        left[:j + 1] = True
    return int(f), int(j), float(gains[f, j]), left


def set_gain(G_f: np.ndarray, H_f: np.ndarray, left: np.ndarray, lam: float,
             mcw: float) -> float:
    """The gain of sending the bins ``left`` of one feature left."""
    return float(_gain(G_f[left].sum(), H_f[left].sum(), G_f.sum(),
                       H_f.sum(), lam, mcw))


# -- histograms, growth, replay ----------------------------------------------

def node_histograms(bins_t: np.ndarray, node: np.ndarray, g: np.ndarray,
                    h: np.ndarray, n_nodes: int, n_bins: int,
                    precision: str = "float64"
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """``[n_nodes, F, n_bins]`` gradient and hessian sums of the rows by
    node, a feature a thread."""
    F = bins_t.shape[0]
    G = np.empty((n_nodes, F, n_bins))
    H = np.empty((n_nodes, F, n_bins))
    g = ref._round_inputs(g, precision)
    h = ref._round_inputs(h, precision)
    base = node.astype(np.int64) * n_bins

    def one(f):
        idx = base + bins_t[f]
        size = n_nodes * n_bins
        G[:, f] = ref._sum_by(idx, g, size, precision).reshape(n_nodes,
                                                               n_bins)
        H[:, f] = ref._sum_by(idx, h, size, precision).reshape(n_nodes,
                                                               n_bins)

    ref._pmap(one, range(F))
    return G, H


def node_histograms_by_class(bins_t: np.ndarray, node: np.ndarray,
                             cls: np.ndarray, g_of: np.ndarray,
                             h_of: np.ndarray, n_nodes: int, n_bins: int
                             ) -> Tuple[np.ndarray, np.ndarray]:
    """The same sums where the rows fall into a few classes of equal
    ``(g, h)``, as before the first tree (one class a label): integer
    counts per (class, node, bin), then one float64 product — exact, one
    pass where the weighted sums make two."""
    F = bins_t.shape[0]
    n_cls = len(g_of)
    G = np.empty((n_nodes, F, n_bins))
    H = np.empty((n_nodes, F, n_bins))
    base = ((cls.astype(np.int64) * n_nodes + node) * n_bins)

    def one(f):
        counts = np.bincount(base + bins_t[f],
                             minlength=n_cls * n_nodes * n_bins
                             ).reshape(n_cls, -1).astype(np.float64)
        G[:, f] = (g_of @ counts).reshape(n_nodes, n_bins)
        H[:, f] = (h_of @ counts).reshape(n_nodes, n_bins)

    ref._pmap(one, range(F))
    return G, H


def _route(bins_t: np.ndarray, node: np.ndarray, feat: np.ndarray,
           left: np.ndarray) -> np.ndarray:
    """Every row one level down: left iff its bin of its node's feature
    is in its node's set (``left`` [N, n_bins])."""
    def chunk(lo):
        nd = node[lo:lo + ref._ROW_CHUNK]
        rows = np.arange(len(nd))
        row_bin = bins_t[:, lo:lo + ref._ROW_CHUNK][feat[nd], rows]
        return 2 * nd + ~left[nd, row_bin]

    return np.concatenate(ref._pmap(
        chunk, range(0, bins_t.shape[1], ref._ROW_CHUNK)))


def grow(bins_t: np.ndarray, g: np.ndarray, h: np.ndarray, used: np.ndarray,
         cfg: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """One depth-wise tree of ``max_depth`` levels on ``(g, h)``, exact
    greedy under the rule: the model's arrays, in float64."""
    depth, n_bins = int(cfg["max_depth"]), int(cfg["n_bins"])
    eta, lam = float(cfg["learning_rate"]), float(cfg["reg_lambda"])
    gamma = float(cfg.get("gamma", 0.0))
    half = 1 << (depth - 1)
    tree = {"feat": np.zeros((depth, half), np.int32),
            "thr": np.zeros((depth, half), np.int32),
            "gain": np.zeros((depth, half)),
            "cats": np.zeros((depth, half, -(-n_bins // WORD)), np.int32)}
    node = np.zeros(bins_t.shape[1], np.int64)
    for level in range(depth):
        n_nodes = 1 << level
        G, H = node_histograms(bins_t, node, g, h, n_nodes, n_bins)
        left = np.ones((n_nodes, n_bins), bool)     # no split: all left
        for i in range(n_nodes):
            f, j, gain, s = best_split(G[i], H[i], used, cfg)
            tree["thr"][level, i] = n_bins - 1
            if gain > gamma:
                tree["feat"][level, i] = f
                tree["thr"][level, i] = s.sum() - 1 if used[f] else j
                tree["gain"][level, i] = gain
                left[i] = s
        tree["cats"][level, :n_nodes] = set_words(left)
        node = _route(bins_t, node, tree["feat"][level], left)
    tree["leaf"] = ref.leaf_values(node, g, h, 1 << depth, eta, lam)
    return tree


def descend_binned(bins_t: np.ndarray, tree: Dict[str, np.ndarray],
                   n_bins: int) -> np.ndarray:
    """Leaf index of every row of a feature-major binned matrix."""
    sets = set_members(tree["cats"], n_bins)
    node = np.zeros(bins_t.shape[1], np.int64)
    for level in range(tree["feat"].shape[0]):
        node = _route(bins_t, node, np.asarray(tree["feat"][level]),
                      sets[level])
    return node


def replay(bins_t: np.ndarray, g: np.ndarray, h: np.ndarray,
           tree: Dict[str, np.ndarray], used: np.ndarray,
           cfg: Dict[str, Any], precision: str = "float64",
           classes=None, rule=None) -> Dict[str, Any]:
    """A tree somebody else built, level by level over its own rows.

    Per split node (``thr < n_bins - 1``), in level order: ``level`` /
    ``index``, its sums ``G`` / ``H``, the best gain over ALL features
    under the rule (``best_gain``) and the feature that reaches it, the
    gain of the RECORDED split (its feature and left set: ``split_gain``),
    the lighter child's hessian (``child_h``), the recorded set's size and
    whether its feature is categorical; then ``leaf_of_row`` and the
    leaves' sums ``leaf_G`` / ``leaf_H``.

    ``classes`` = ``(cls, g_of, h_of)`` says the rows fall into a few
    classes of equal gradients (``g`` / ``h`` are ``g_of[cls]`` /
    ``h_of[cls]``): the sums are then integer counts times those.
    ``rule`` = ``(bins_t, used)`` takes ``best_gain`` from ANOTHER
    binning of the same rows — a tree grown on codes read as an order,
    held to what the rule would have found at each of its nodes."""
    n_bins = int(cfg["n_bins"])
    lam, mcw = float(cfg["reg_lambda"]), float(cfg["min_child_weight"])
    sets = set_members(tree["cats"], n_bins)
    feat, thr = np.asarray(tree["feat"]), np.asarray(tree["thr"])
    node = np.zeros(bins_t.shape[1], np.int64)
    rows: List[Dict[str, Any]] = []
    for level in range(feat.shape[0]):
        n_nodes = 1 << level
        def sums(bt):
            if classes is not None and precision == "float64":
                return node_histograms_by_class(bt, node, *classes, n_nodes,
                                                n_bins)
            return node_histograms(bt, node, g, h, n_nodes, n_bins, precision)

        G, H = sums(bins_t)
        Gr, Hr, used_r = (G, H, used) if rule is None else (
            *sums(rule[0]), rule[1])
        for i in range(n_nodes):
            if thr[level, i] >= n_bins - 1:
                continue
            f = int(feat[level, i])
            best_f, _, best, _ = best_split(Gr[i], Hr[i], used_r, cfg)
            s = sets[level, i]
            hl = H[i, f][s].sum()
            rows.append({
                "level": level, "index": i, "feat": f,
                "G": G[i, 0].sum(), "H": H[i, 0].sum(),
                "best_gain": best, "best_feat": best_f,
                "split_gain": set_gain(G[i, f], H[i, f], s, lam, 0.0),
                "child_h": min(hl, H[i, f].sum() - hl),
                "cat": bool(used[f]), "set_size": int(s.sum()),
                "set_whole": bool(used[f]) and int(s[:used[f]].sum())
                in (0, int(used[f])),
            })
        node = _route(bins_t, node, feat[level], sets[level])
    n_leaf = 1 << feat.shape[0]
    gr, hr = ref._round_inputs(g, precision), ref._round_inputs(h, precision)
    out = {k: np.asarray([r[k] for r in rows]) for k in rows[0]} if rows \
        else {}
    out.update(leaf_of_row=node,
               leaf_G=ref._sum_by(node, gr, n_leaf, precision),
               leaf_H=ref._sum_by(node, hr, n_leaf, precision))
    return out


# -- raw rows ----------------------------------------------------------------

def ensemble_margin(X: np.ndarray, cuts: np.ndarray,
                    feature_types: Sequence[str], trees, base_score: float,
                    n_bins: int) -> np.ndarray:
    """Raw margin of raw rows under a list of trees: the rows binned by
    :func:`bin_rows`, then one plain descent after another."""
    bins_t = np.ascontiguousarray(bin_rows(X, cuts, feature_types).T)
    margin = np.full(X.shape[0], float(base_score))
    for t in trees:
        margin = margin + np.asarray(t["leaf"], np.float64)[
            descend_binned(bins_t, t, n_bins)]
    return margin
