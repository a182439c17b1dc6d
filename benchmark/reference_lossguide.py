"""The plain reference of LEAF-WISE growth: float64 numpy, importing
nothing of the program.

The rule, as XGBoost's ``grow_policy=lossguide`` and LightGBM publish it:
a priority queue over the open leaves; the leaf whose best split has the
highest gain is split next (ties: the lowest node id), while that gain is
above ``gamma`` and both children hold ``min_child_weight`` of hessian,
until the tree has ``max_leaves`` leaves (or a leaf sits at ``max_depth``,
0 = no cap).  Splits are exact-greedy over the ``n_bins``-bin histograms
(:func:`reference.split_gains`: XGBoost's gain, ties to the lowest
feature, then the lowest threshold); a leaf's value is
``-eta * G / (H + lambda)``.

A tree is a NODE LIST, the model's own format for such trees: arrays of
``2 * max_leaves - 1`` entries, node 0 the root, expansion ``k`` (0-based)
making nodes ``2k+1`` (left: ``bin <= thr``) and ``2k+2`` (right).
``left``/``right`` are a split node's children (-1: none), ``feat`` /
``thr`` / ``gain`` its split (0 / ``n_bins - 1`` / 0 elsewhere), ``value``
a leaf's value (0 elsewhere).  The node ids therefore SAY the expansion
order.

:func:`grow` builds such a tree from ``(bins_t, g, h)``; :func:`replay`
takes a tree somebody else built and recomputes, over all the rows, every
node's float64 sums, row count, best split and gain — what
``checks_lossguide`` holds the timed fit's first tree against.  The
replay descends the rows in chunks and sums per feature on a few threads
(``reference._pmap``): 24M x 28 rows take a fraction of a minute and a
few GB.
"""

from __future__ import annotations

import heapq
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from benchmark import reference as ref

KEYS = ("feat", "thr", "gain", "left", "right", "value")


def empty_tree(max_leaves: int, n_bins: int) -> Dict[str, np.ndarray]:
    m = 2 * max_leaves - 1
    return {"feat": np.zeros(m, np.int32),
            "thr": np.full(m, n_bins - 1, np.int32),
            "gain": np.zeros(m, np.float64),
            "left": np.full(m, -1, np.int32),
            "right": np.full(m, -1, np.int32),
            "value": np.zeros(m, np.float64)}


def best_split(G: np.ndarray, H: np.ndarray, lam: float, mcw: float
               ) -> Tuple[int, int, float]:
    """``(feature, threshold, gain)`` of one node's ``[F, n_bins]`` sums;
    gain ``-inf`` where no threshold leaves two children of
    ``min_child_weight``."""
    gains = ref.split_gains(G, H, lam, mcw)
    f, t = np.unravel_index(int(np.argmax(gains)), gains.shape)
    return int(f), int(t), float(gains[f, t])


def node_histograms(bins_t: np.ndarray, slot: np.ndarray, g: np.ndarray,
                    h: np.ndarray, n_slots: int, n_bins: int,
                    precision: str = "float64"
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """``[n_slots, F, n_bins]`` gradient and hessian sums of the rows by
    ``slot`` (one slot a node; rows of slot -1 are in none), a feature a
    thread."""
    F = bins_t.shape[0]
    G = np.empty((n_slots, F, n_bins))
    H = np.empty((n_slots, F, n_bins))
    keep = slot >= 0
    if not keep.all():
        bins_t, slot, g, h = bins_t[:, keep], slot[keep], g[keep], h[keep]
    g, h = ref._round_inputs(g, precision), ref._round_inputs(h, precision)
    base = slot.astype(np.int64) * n_bins

    def one(f):
        idx = base + bins_t[f]
        G[:, f] = ref._sum_by(idx, g, n_slots * n_bins, precision
                              ).reshape(n_slots, n_bins)
        H[:, f] = ref._sum_by(idx, h, n_slots * n_bins, precision
                              ).reshape(n_slots, n_bins)

    ref._pmap(one, range(F))
    return G, H


# -- growing ------------------------------------------------------------------

def grow(bins_t: np.ndarray, g: np.ndarray, h: np.ndarray, n_bins: int,
         max_leaves: int, lam: float, mcw: float, eta: float,
         gamma: float = 0.0, max_depth: int = 0) -> Dict[str, np.ndarray]:
    """One leaf-wise tree by the published rule, straightforwardly: every
    new node's histogram from its own rows."""
    g, h = np.asarray(g, np.float64), np.asarray(h, np.float64)
    tree = empty_tree(max_leaves, n_bins)
    node = np.zeros(bins_t.shape[1], np.int64)
    sums: Dict[int, Tuple[float, float]] = {}
    cand: Dict[int, Tuple[int, int, float]] = {}
    depth = {0: 0}
    heap: List[Tuple[float, int]] = []

    def open_leaf(i: int) -> None:
        G, H = node_histograms(bins_t, np.where(node == i, 0, -1), g, h, 1,
                               n_bins)
        sums[i] = (float(G[0, 0].sum()), float(H[0, 0].sum()))
        cand[i] = best_split(G[0], H[0], lam, mcw)
        if cand[i][2] > gamma and not (max_depth and depth[i] >= max_depth):
            heapq.heappush(heap, (-cand[i][2], i))

    open_leaf(0)
    for k in range(max_leaves - 1):
        if not heap:
            break
        _, i = heapq.heappop(heap)          # highest gain, then lowest id
        f, t, gain = cand[i]
        lc, rc = 2 * k + 1, 2 * k + 2
        tree["feat"][i], tree["thr"][i], tree["gain"][i] = f, t, gain
        tree["left"][i], tree["right"][i] = lc, rc
        mine = node == i
        node[mine] = np.where(bins_t[f, mine] > t, rc, lc)
        depth[lc] = depth[rc] = depth.pop(i) + 1
        del sums[i]
        open_leaf(lc)
        open_leaf(rc)
    for i, (G, H) in sums.items():
        tree["value"][i] = -eta * G / (H + lam)
    return tree


# -- reading a tree somebody built -------------------------------------------

def leaves_of(tree: Dict[str, np.ndarray]) -> np.ndarray:
    """Ids of the leaves the root reaches, ascending."""
    left, right = np.asarray(tree["left"]), np.asarray(tree["right"])
    out, todo = [], [0]
    while todo:
        i = todo.pop()
        if left[i] > 0:
            todo += [int(left[i]), int(right[i])]
        else:
            out.append(i)
    return np.asarray(sorted(out), np.int64)


def depth_of(tree: Dict[str, np.ndarray]) -> int:
    """Splits on the longest path from the root to a leaf."""
    left, right = np.asarray(tree["left"]), np.asarray(tree["right"])
    deepest, todo = 0, [(0, 0)]
    while todo:
        i, d = todo.pop()
        deepest = max(deepest, d)
        if left[i] > 0:
            todo += [(int(left[i]), d + 1), (int(right[i]), d + 1)]
    return deepest


def expansion_order(tree: Dict[str, np.ndarray]) -> List[int]:
    """The split nodes in the order they were split: the node whose left
    child is ``2k+1`` was expansion ``k``.  Raises if the ids do not say
    an order (children not ``2k+1`` / ``2k+2``, a parent split after its
    child)."""
    left, right = np.asarray(tree["left"]), np.asarray(tree["right"])
    by_k = {}
    for i in np.flatnonzero(left > 0):
        lc, rc = int(left[i]), int(right[i])
        if lc % 2 != 1 or rc != lc + 1 or lc <= i:
            raise ValueError(f"node {i}: children {lc}, {rc} are not an "
                             f"expansion's 2k+1, 2k+2")
        by_k[(lc - 1) // 2] = int(i)
    if sorted(by_k) != list(range(len(by_k))):
        raise ValueError(f"expansions {sorted(by_k)} leave gaps")
    return [by_k[k] for k in range(len(by_k))]


def descend_binned(bins_t: np.ndarray, tree: Dict[str, np.ndarray]
                   ) -> np.ndarray:
    """Leaf node id of every row of a feature-major binned matrix
    ``[F, n]``: children are followed until no row moves."""
    feat, thr, left, right = (np.asarray(tree[k]) for k in
                              ("feat", "thr", "left", "right"))

    def chunk(lo):
        part = bins_t[:, lo:lo + ref._ROW_CHUNK]
        node = np.zeros(part.shape[1], np.int64)
        moving = np.flatnonzero(left[node] > 0)
        while len(moving):
            at = node[moving]
            row_bin = part[feat[at], moving]
            node[moving] = np.where(row_bin > thr[at], right[at], left[at])
            moving = moving[left[node[moving]] > 0]
        return node

    return np.concatenate(ref._pmap(
        chunk, range(0, bins_t.shape[1], ref._ROW_CHUNK)))


def descend_raw(X: np.ndarray, cuts: np.ndarray, tree: Dict[str, np.ndarray]
                ) -> np.ndarray:
    """Leaf node id of raw rows ``[n, F]``: ``bin > thr`` is ``x >=
    cuts[f, thr]`` (bin = number of cuts <= x)."""
    feat, thr, left, right = (np.asarray(tree[k]) for k in
                              ("feat", "thr", "left", "right"))
    node = np.zeros(X.shape[0], np.int64)
    moving = np.flatnonzero(left[node] > 0)
    while len(moving):
        at = node[moving]
        go_right = X[moving, feat[at]] >= cuts[feat[at], thr[at]]
        node[moving] = np.where(go_right, right[at], left[at])
        moving = moving[left[node[moving]] > 0]
    return node


def ensemble_margin(X: np.ndarray, cuts: np.ndarray,
                    trees: Sequence[Dict[str, np.ndarray]], base_score: float,
                    precision: str = "float64") -> np.ndarray:
    """Raw margin of raw rows under a list of node-list trees, one tree
    after another; the control rounds every leaf value and every partial
    sum to bfloat16."""
    cuts = np.asarray(cuts, np.float64)
    X = np.asarray(X, np.float64)
    margin = np.full(X.shape[0], float(base_score))
    nodes = ref._pmap(lambda t: descend_raw(X, cuts, t), trees)
    for t, node in zip(trees, nodes):
        add = np.asarray(t["value"], np.float64)[node]
        if precision == "float64":
            margin = margin + add
        else:
            margin = ref.to_bf16(margin + ref.to_bf16(add))
    return margin


def leaf_values(bins_t: np.ndarray, g: np.ndarray, h: np.ndarray,
                tree: Dict[str, np.ndarray], eta: float, lam: float,
                precision: str = "float64"
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """``(leaf ids, -eta * G / (H + lambda) of each, its H, every row's
    leaf id)``: sums over each leaf's own rows."""
    leaves = leaves_of(tree)
    at = descend_binned(bins_t, tree)
    slot = np.searchsorted(leaves, at)
    G = ref._sum_by(slot, ref._round_inputs(g, precision), len(leaves),
                    precision)
    H = ref._sum_by(slot, ref._round_inputs(h, precision), len(leaves),
                    precision)
    return leaves, -eta * G / (H + lam), H, at


def replay(bins_t: np.ndarray, g: np.ndarray, h: np.ndarray,
           tree: Dict[str, np.ndarray], n_bins: int, lam: float, mcw: float,
           precision: str = "float64") -> Dict[str, Any]:
    """Every node of ``tree`` recomputed over the rows: per node (arrays
    over the node list; unreached entries NaN / 0) the sums ``G`` / ``H``,
    the row count ``rows``, the best split ``best_feat`` / ``best_thr`` /
    ``best_gain`` of its own histogram, and ``split_gain``, the gain of
    the split the tree RECORDS there (split nodes).  A leaf's histogram is
    summed from its rows, a split node's is its children's added up.
    ``leaf_of_row`` is every row's leaf."""
    g, h = np.asarray(g, np.float64), np.asarray(h, np.float64)
    feat, thr, left, right = (np.asarray(tree[k]) for k in
                              ("feat", "thr", "left", "right"))
    m = len(left)
    leaves = leaves_of(tree)
    at = descend_binned(bins_t, tree)
    hist: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
    slot = np.searchsorted(leaves, at)
    Gs, Hs = node_histograms(bins_t, slot, g, h, len(leaves), n_bins,
                             precision)
    counts = np.bincount(slot, minlength=len(leaves))
    rows = np.zeros(m, np.int64)
    for j, i in enumerate(leaves):
        hist[int(i)] = (Gs[j], Hs[j])
        rows[i] = counts[j]
    order = expansion_order(tree)
    for i in reversed(order):               # children before parents
        (gl, hl), (gr, hr) = hist[int(left[i])], hist[int(right[i])]
        hist[i] = (gl + gr, hl + hr)
        rows[i] = rows[left[i]] + rows[right[i]]
    out = {"G": np.full(m, np.nan), "H": np.full(m, np.nan), "rows": rows,
           "best_feat": np.zeros(m, np.int64),
           "best_thr": np.zeros(m, np.int64),
           "best_gain": np.full(m, np.nan), "split_gain": np.full(m, np.nan),
           "leaf_of_row": at, "leaves": leaves, "order": order}
    for i, (G, H) in hist.items():
        out["G"][i], out["H"][i] = G[0].sum(), H[0].sum()
        gains = ref.split_gains(G, H, lam, mcw)
        f, t = np.unravel_index(int(np.argmax(gains)), gains.shape)
        out["best_feat"][i], out["best_thr"][i] = f, t
        out["best_gain"][i] = gains[f, t]
        if left[i] > 0 and thr[i] < n_bins - 1:
            out["split_gain"][i] = gains[feat[i], thr[i]]
    return out


def needed_rows(rep: Dict[str, Any], tree: Dict[str, np.ndarray]) -> int:
    """Rows the histogram builds of this tree NEED, whatever builds them:
    all rows for the root, and for every expansion the rows of its
    smaller child (the other child is the parent less that one)."""
    left, right = np.asarray(tree["left"]), np.asarray(tree["right"])
    rows = rep["rows"]
    return int(rows[0] + sum(min(rows[left[i]], rows[right[i]])
                             for i in rep["order"]))


def from_levels(tree: Dict[str, np.ndarray], n_bins: int
                ) -> Dict[str, np.ndarray]:
    """A DEPTH-WISE tree (``feat`` / ``thr`` / ``gain`` ``[depth, half]``,
    ``leaf`` ``[2**depth]``) as a node list numbered level by level, a
    node that does not split (``thr == n_bins - 1``) a leaf: what a
    depth-wise grower hands in under the same leaf count."""
    feat, thr = np.asarray(tree["feat"]), np.asarray(tree["thr"])
    gain, leaf = np.asarray(tree["gain"]), np.asarray(tree["leaf"])
    depth = feat.shape[0]
    splits = int(sum((thr[lv, :1 << lv] < n_bins - 1).sum()
                     for lv in range(depth)))
    out = empty_tree(splits + 1, n_bins)
    k = 0
    todo = [(0, 0, 0)]                       # (node id, level, index)
    while todo:
        i, lv, j = todo.pop(0)               # level order
        if lv < depth and thr[lv, j] < n_bins - 1:
            lc, rc = 2 * k + 1, 2 * k + 2
            k += 1
            out["feat"][i], out["thr"][i] = feat[lv, j], thr[lv, j]
            out["gain"][i] = gain[lv, j]
            out["left"][i], out["right"][i] = lc, rc
            todo += [(lc, lv + 1, 2 * j), (rc, lv + 1, 2 * j + 1)]
        else:
            # a node that stops early hands its rows left all the way down
            out["value"][i] = leaf[j << (depth - lv)]
    return out
