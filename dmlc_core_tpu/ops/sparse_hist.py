"""Sparse histogram substrate: ragged per-feature bins over PRESENT entries.

The dense engine (``ops/histogram.py``) bins every cell of an ``[n, F]``
matrix — impossible for LibSVM's natural workloads (bag-of-words /
hashed one-hot, F ≈ 10⁴–10⁶, density < 1%), where the bin matrix alone
would be 10–1000 GB.  This module is the sparsity-aware substrate
(SURVEY.md §7 hard part (a), BASELINE config 3 "sparse CSR"; XGBoost's
``SparsePage`` + sparsity-aware split finding re-derived for XLA):

* **Ragged global bin space**: feature ``j`` owns bins
  ``[bin_ptr[j], bin_ptr[j+1])`` — per-feature cut counts adapt to the
  feature's distinct values (a binary indicator takes 2 bins, not 256),
  so ``total_bins = Σ_j (ncuts_j + 1)`` stays ~O(nnz-distinct), not
  ``F × max_bins``.
* **Histograms by segment-sum over entries**: one ``jax.ops.segment_sum``
  of per-row gradients over ``node(row) × total_bins + gb(entry)`` per
  level — O(nnz) work, static shapes, no densification ever.
* **Absent = missing**: a node's absent mass for feature j is
  ``G_node − Σ present_j`` (no storage at all); the split scan evaluates
  both default directions exactly like the dense NaN engine
  (models/histgbt.py missing mode), so sparse-absent semantics equal
  XGBoost's.

Everything here is representation-level (host numpy for the one-time
cut/bin passes, jitted segment-sums for the per-round work); the tree
loop lives in ``models/histgbt_sparse.py``.

Measured floor (one v5e, pre-PR 1 code, 24M nnz, TB=1.6M, fetch-synced;
not reproduced since): histogram scatter ~1.1 s/level,
routing ~1.0 s/level (now ~halved by the single coded scatter), split
scan 0.3 s, totals negligible.  Dead end, kept so it is not re-derived:
packing (g, h) into ONE complex64 scatter — ``segment_sum`` over
complex64 raises ``UNIMPLEMENTED: TPU backend error``; the apparent 2×
in a slice-synced microbenchmark was dead-code elimination.  The honest
remaining lever is a Pallas sorted-segment reduction (entries pre-sorted
by gb are static across rounds), left for a future round.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from dmlc_core_tpu.base.logging import CHECK

__all__ = ["SparseCuts", "build_sparse_cuts", "sparse_cut_candidates",
           "merge_sparse_cut_candidates", "bin_sparse_entries",
           "csr_rows", "level_histogram", "node_totals",
           "sparse_best_split", "route_level"]


class SparseCuts(NamedTuple):
    """Ragged per-feature quantile cuts.

    ``cut_vals[cut_ptr[j]:cut_ptr[j+1]]`` are feature j's strictly
    increasing cut points; its local bin of value v is
    ``#cuts_j ≤ v  ∈ [0, ncuts_j]`` and its global bin is
    ``bin_ptr[j] + local`` with ``bin_ptr[j+1] − bin_ptr[j] =
    ncuts_j + 1``.  ``feat_of_bin[gb]`` inverts the layout.
    """
    cut_vals: np.ndarray     # [total_cuts] f32
    cut_ptr: np.ndarray      # [F+1] int64
    bin_ptr: np.ndarray      # [F+1] int64
    feat_of_bin: np.ndarray  # [total_bins] int32

    @property
    def n_features(self) -> int:
        return len(self.cut_ptr) - 1

    @property
    def total_bins(self) -> int:
        return int(self.bin_ptr[-1])


def sparse_cut_candidates(cols: np.ndarray, values: np.ndarray,
                          n_features: int,
                          max_bins: int = 256) -> np.ndarray:
    """Per-feature cut CANDIDATES ``[F, max_bins-1]`` (f32; all-NaN row
    for a feature with no local entries), fully vectorized.

    One ``lexsort`` of the nnz entries by (feature, value), then every
    feature's candidates are gathered at evenly spaced ranks of its own
    segment — no per-feature Python loop (F can be 10⁶).  Unweighted
    ranks (the sparse path's v1 contract; the dense engine keeps
    weighted sketches).  This fixed-shape matrix is also the
    distributed message: workers allgather their candidate matrices and
    :func:`merge_sparse_cut_candidates` re-quantiles the union —
    the sparse analogue of the dense cut allgather-merge.
    """
    CHECK(max_bins >= 2, "need at least 2 bins")
    cols = np.asarray(cols)
    values = np.asarray(values, np.float32)
    CHECK(len(cols) == len(values), "cols/values length mismatch")
    if len(cols):
        CHECK(int(cols.max()) < n_features, "feature index out of range")
        CHECK(int(cols.min()) >= 0, "negative feature index")
        CHECK(np.isfinite(values).all(),
              "sparse values must be finite (absent entries ARE the "
              "missing mass; explicit NaN has no sparse meaning)")
    order = np.lexsort((values, cols))
    cv = values[order]
    counts = np.bincount(cols, minlength=n_features)          # [F]
    starts = np.concatenate([[0], np.cumsum(counts)])         # [F+1]
    nb = max_bins - 1                                         # cut slots
    # candidate ranks: k/nb quantile positions inside each segment
    k = np.arange(1, nb + 1)                                  # [nb]
    m = counts[:, None]                                       # [F, 1]
    idx = starts[:-1, None] + np.minimum(
        np.ceil(k[None, :] * m / (nb + 1)).astype(np.int64),
        np.maximum(m - 1, 0))
    cand = cv[np.minimum(idx, len(cv) - 1 if len(cv) else 0)] \
        if len(cv) else np.zeros((n_features, nb), np.float32)  # [F, nb]
    cand[counts == 0] = np.nan
    return cand


def merge_sparse_cut_candidates(cands: np.ndarray) -> SparseCuts:
    """Merge ``[W, F, max_bins-1]`` worker candidate matrices into
    ragged :class:`SparseCuts`.

    Per feature the union of the workers' candidate points is
    re-quantiled onto the candidates' own grid width (NaN rows — workers
    whose shard lacked the feature — contribute nothing; like the dense
    ``merge_summaries``, worker summaries weigh equally, which is exact
    for the similar-size shards data-parallel splits produce).  With
    ``W = 1`` the merge is the identity on the candidates, so single-
    and multi-worker paths share one code path.  De-duplication keeps
    strictly increasing runs; a feature with no finite candidate
    anywhere keeps 0 cuts (1 bin, never a split).
    """
    cands = np.asarray(cands, np.float32)
    W, F, nb = cands.shape
    pts = np.sort(cands.transpose(1, 0, 2).reshape(F, W * nb), axis=1)
    m = (~np.isnan(pts)).sum(axis=1, keepdims=True)           # [F, 1]
    k = np.arange(1, nb + 1)                                  # [nb]
    # candidate j of a worker sits at quantile (j+1)/(nb+1) of its
    # shard; selecting rank ceil(k·(m+1)/(nb+1))−1 of the union puts
    # target k/(nb+1) back on the same grid — and makes W=1 the exact
    # identity on the candidates
    idx = np.clip(np.ceil(k[None, :] * (m + 1) / (nb + 1)).astype(
        np.int64) - 1, 0, np.maximum(m - 1, 0))
    cand = np.take_along_axis(pts, idx, axis=1)               # [F, nb]
    # keep strictly increasing runs only; empty features keep 0 cuts.
    # A cut equal to the feature's MINIMUM value is useless as a
    # threshold only if nothing sorts below it — but bin-of-value uses
    # "#cuts ≤ v", so any duplicate-free subset is valid.
    keep = np.ones_like(cand, bool)
    keep[:, 1:] = cand[:, 1:] > cand[:, :-1]
    keep[m[:, 0] == 0] = False
    keep &= ~np.isnan(cand)
    ncuts = keep.sum(axis=1)                                  # [F]
    cut_ptr = np.concatenate([[0], np.cumsum(ncuts)])
    cut_vals = cand[keep].astype(np.float32)
    widths = ncuts + 1
    bin_ptr = np.concatenate([[0], np.cumsum(widths)])
    feat_of_bin = np.repeat(np.arange(F, dtype=np.int32), widths)
    return SparseCuts(cut_vals, cut_ptr.astype(np.int64),
                      bin_ptr.astype(np.int64), feat_of_bin)


def build_sparse_cuts(cols: np.ndarray, values: np.ndarray, n_features: int,
                      max_bins: int = 256) -> SparseCuts:
    """Single-worker cuts: candidates → (W=1) merge.  One code path
    with the distributed build, which allgathers the candidate stage."""
    cand = sparse_cut_candidates(cols, values, n_features, max_bins)
    return merge_sparse_cut_candidates(cand[None])


def bin_sparse_entries(cols: np.ndarray, values: np.ndarray,
                       cuts: SparseCuts) -> np.ndarray:
    """Global bin id per entry (vectorized grouped searchsorted).

    The grouped "``#cuts_j ≤ v``" count has no direct numpy form, so it
    is computed by MERGING cuts and entries per feature: sort the
    combined multiset by (feature, value, kind) with cuts ordered before
    entries at equal value; each entry's local bin is then the running
    cut count within its feature segment.  O((nnz+C)·log) once per
    dataset.
    """
    cols = np.asarray(cols)
    values = np.asarray(values, np.float32)
    C = len(cuts.cut_vals)
    n = len(values)
    if n == 0:
        return np.zeros(0, np.int32)
    cut_cols = np.repeat(np.arange(cuts.n_features),
                         np.diff(cuts.cut_ptr)).astype(cols.dtype)
    all_cols = np.concatenate([cut_cols, cols])
    all_vals = np.concatenate([cuts.cut_vals, values])
    kind = np.concatenate([np.zeros(C, np.int8), np.ones(n, np.int8)])
    order = np.lexsort((kind, all_vals, all_cols))
    is_cut = kind[order] == 0
    run_cuts = np.cumsum(is_cut)                     # cuts so far, global
    # cuts before each feature's segment start = cut_ptr[feature]
    pos_of_entry = np.empty(C + n, np.int64)
    pos_of_entry[order] = np.arange(C + n)
    entry_pos = pos_of_entry[C:]
    local = run_cuts[entry_pos] - cuts.cut_ptr[cols]
    gb = cuts.bin_ptr[cols] + local
    return gb.astype(np.int32)


def csr_rows(indptr: np.ndarray) -> np.ndarray:
    """Row index per entry from a CSR indptr (int32)."""
    indptr = np.asarray(indptr)
    return np.repeat(np.arange(len(indptr) - 1, dtype=np.int32),
                     np.diff(indptr))


# ---------------------------------------------------------------------------
# jitted per-level kernels
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_build", "total_bins", "level"))
def level_histogram(row_e, gb_e, node, g, h, *, n_build: int,
                    total_bins: int, level: int):
    """Left-child gradient histograms ``[2, n_build, total_bins]`` for one
    level, by ONE segment-sum over the nnz entries.

    ``node`` [n] is each row's node at this level (−1 = padding).  At
    level 0 every row builds node 0; deeper levels build LEFT children
    only (sibling subtraction: right = parent − left, like the dense
    engines) — entries whose row's node is odd (a right child) or
    invalid dump into an overflow segment that is sliced away.
    """
    nd = node
    if level > 0:
        nd = jnp.where((nd >= 0) & (nd % 2 == 0), nd >> 1, -1)
    n_entry = nd[row_e]                                  # [nnz]
    valid = n_entry >= 0
    seg = jnp.where(valid, n_entry * total_bins + gb_e,
                    n_build * total_bins)
    ge = jnp.where(valid, g[row_e], 0.0)
    he = jnp.where(valid, h[row_e], 0.0)
    hist_g = jax.ops.segment_sum(ge, seg,
                                 num_segments=n_build * total_bins + 1)
    hist_h = jax.ops.segment_sum(he, seg,
                                 num_segments=n_build * total_bins + 1)
    return jnp.stack([hist_g[:-1].reshape(n_build, total_bins),
                      hist_h[:-1].reshape(n_build, total_bins)])


@partial(jax.jit, static_argnames=("n_nodes",))
def node_totals(node, g, h, *, n_nodes: int):
    """Per-node TOTAL g/h sums over all rows (present + absent mass) —
    ``[2, n_nodes]``; padding rows (node < 0) dump into the overflow."""
    safe = jnp.where(node >= 0, node, n_nodes)
    return jnp.stack([
        jax.ops.segment_sum(g, safe, num_segments=n_nodes + 1)[:-1],
        jax.ops.segment_sum(h, safe, num_segments=n_nodes + 1)[:-1]])


@partial(jax.jit, static_argnames=("n_dense", "b_max", "lam", "gamma",
                                   "mcw", "alpha"))
def sparse_best_split(hist, totals, bin_ptr_d, feat_of_bin_d, last_mask,
                      dense_pos_d, *, n_dense: int, b_max: int,
                      lam: float, gamma: float, mcw: float,
                      alpha: float = 0.0):
    """Sparsity-aware split chooser over the ragged flat bin space.

    ``hist`` [2, N, TB] (present-entry g/h per global bin), ``totals``
    [2, N] (ALL rows), ``bin_ptr_d`` [F+1], ``feat_of_bin_d`` [TB],
    ``last_mask`` [TB] (True at each feature's LAST bin — not a valid
    threshold), ``dense_pos_d`` [TB] (each global bin's slot in the
    feature-padded ``[F, b_max]`` layout, ``n_dense = F · b_max``).
    For every candidate bin the absent mass ``totals −
    feature_present`` is tried on both sides (the learned default
    direction).  Returns (feat [N], thr_local [N], dir [N] (1 =
    missing left), gain [N]) with the dense engine's degenerate
    convention: gain ≤ gamma → feat 0 / thr = width(f0)−1 / dir 1
    (everyone, missing included, goes left).

    Numerics: within-feature prefixes are computed by scattering the
    ragged hist into the padded per-feature layout and cumsumming along
    the SHORT minor axis — each feature's prefix sees only its OWN
    mass.  A single global cumsum with start-subtraction (the first
    formulation) rides the whole dataset's magnitude (f32 ulp ~0.25 at
    a 10⁶ Hessian prefix), drowning rare features; a segmented
    associative_scan is exact but measured ~10× slower than cumsum on
    this backend (bench went 110 s → timeout).  The scatter/gather pair
    is memory-bound like the cumsum itself.
    """
    g, h = hist[0], hist[1]                              # [N, TB]
    N, TB = g.shape

    def seg_cumsum(x):
        dense = jnp.zeros((N, n_dense), x.dtype).at[:, dense_pos_d].set(x)
        cum = jnp.cumsum(dense.reshape(N, n_dense // b_max, b_max),
                         axis=2).reshape(N, n_dense)
        return cum[:, dense_pos_d]

    gl = seg_cumsum(g)                                   # [N, TB]
    hl = seg_cumsum(h)
    # the feature's TOTAL present mass = its prefix at its LAST bin
    end1 = bin_ptr_d[feat_of_bin_d + 1] - 1              # [TB] last bin
    Tg = gl[:, end1]
    Th = hl[:, end1]
    gt = totals[0][:, None]                              # [N, 1] all rows
    ht = totals[1][:, None]
    miss_g = gt - Tg                                     # absent mass
    miss_h = ht - Th

    # the ONE home of XGBoost's ThresholdL1 semantics (alpha=0 keeps the
    # exact G**2 primitive) — shared with the dense engines
    from dmlc_core_tpu.models.gbt_split import _soft_threshold

    if alpha > 0.0:
        def _score(G, H):
            t = _soft_threshold(G, alpha)
            return t * t / (H + lam)
    else:
        def _score(G, H):
            return G ** 2 / (H + lam)

    def side_gain(gl_, hl_):
        gr_ = gt - gl_
        hr_ = ht - hl_
        gn = _score(gl_, hl_) + _score(gr_, hr_) - _score(gt, ht)
        ok = (hl_ >= mcw) & (hr_ >= mcw)
        return jnp.where(ok, gn, -jnp.inf)

    gain_r = side_gain(gl, hl)                           # missing right
    gain_l = side_gain(gl + miss_g, hl + miss_h)         # missing left
    gain = jnp.maximum(gain_r, gain_l)
    dir_l = gain_l > gain_r
    gain = jnp.where(last_mask[None, :], -jnp.inf, gain)
    best = jnp.argmax(gain, axis=1)                      # [N] global bin
    best_gain = jnp.take_along_axis(gain, best[:, None], axis=1)[:, 0]
    feat = feat_of_bin_d[best]
    thr = (best - bin_ptr_d[feat]).astype(jnp.int32)
    dirv = jnp.take_along_axis(dir_l, best[:, None], axis=1)[:, 0]
    # XGBoost convention, matching the dense chooser (gbt_split.py): the
    # acceptance test and the reported gain both carry the ½ factor —
    # the same `gamma` must mean the same thing whichever engine the
    # sklearn wrappers route to, and importance_type="gain" must agree
    ok = 0.5 * best_gain > gamma
    width0 = (bin_ptr_d[1] - bin_ptr_d[0]).astype(jnp.int32)
    feat = jnp.where(ok, feat, 0).astype(jnp.int32)
    thr = jnp.where(ok, thr, width0 - 1)
    dirv = jnp.where(ok, dirv, True)
    gain_out = jnp.where(ok, 0.5 * best_gain, 0.0)
    return feat, thr, dirv, gain_out


@jax.jit
def route_level(row_e, gb_e, node, feat, thr, dirv, bin_ptr_d,
                feat_of_bin_d):
    """Advance every row one level down using only PRESENT entries.

    Default: rows follow their node's missing direction.  Rows that DO
    have the split feature override via two conflict-free segment-sums
    (each row holds at most one entry of a given feature): ``cnt[r]``
    flags a present entry of the split feature, ``side[r]`` its
    left/right verdict.  Padding rows stay −1.
    """
    n = node.shape[0]
    valid = node >= 0
    safe = jnp.where(valid, node, 0)
    # default child: missing direction (dir=1 → left)
    default = 2 * safe + jnp.where(dirv[safe], 0, 1)
    # entry overrides — ONE integer-coded scatter (a row has at most
    # one entry of its split feature, so code ∈ {0, 2, 3} after the
    # sum: bit 1 = "entry present", bit 0 = its right-verdict.  Two
    # separate segment_sums cost ~2× here; the scatter is the
    # per-level floor at 10⁷+ nnz — measured 1.0 s → ~0.55 s at 24M).
    n_e = node[row_e]
    ok_e = n_e >= 0
    safe_e = jnp.where(ok_e, n_e, 0)
    split_gb = bin_ptr_d[feat[safe_e]] + thr[safe_e]     # [nnz] threshold
    match = ok_e & (feat_of_bin_d[gb_e] == feat[safe_e])
    side = match & (gb_e > split_gb)                     # right verdict
    seg = jnp.where(ok_e, row_e, n)
    code = jax.ops.segment_sum(
        match.astype(jnp.int32) * 2 + side.astype(jnp.int32), seg,
        num_segments=n + 1)[:-1]
    routed = 2 * safe + jnp.where(code >= 2, code & 1, default - 2 * safe)
    return jnp.where(valid, routed, -1)
