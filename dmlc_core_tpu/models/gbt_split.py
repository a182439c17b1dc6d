"""Shared tree-growth primitives: split chooser, row routing, leaf sums.

The pieces both HistGBT engines (the in-core shard_map round program and
the external-memory chunk loop) are built from — split out of
``histgbt.py`` so the engines can live in sibling modules without a
circular import.  Functional parity: XGBoost hist's split evaluator
(reference ``src/tree/updater_quantile_hist``-class logic; SURVEY.md §1)
re-derived for XLA: static shapes, level-wise complete trees, gain math
vectorized over [nodes, features, bins] on device.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from dmlc_core_tpu.base import metrics as _metrics
from dmlc_core_tpu.base.logging import CHECK, log_fatal
from dmlc_core_tpu.ops.histogram import select_feature_bins

__all__ = ["_make_best_split", "_advance_node", "_leaf_sums",
           "_soft_threshold", "_maybe_l1", "_host_bin_requested",
           "_host_bin_t", "gbt_metrics"]

_GM = None


def gbt_metrics():
    """Shared GBT instrument handles (every engine — in-core, external,
    sparse — reports into the same series, separated by the ``engine``
    label)."""
    global _GM
    if _GM is None:
        r = _metrics.default_registry()
        _GM = {
            "rounds": r.counter("gbt_rounds_total",
                                "boosting rounds completed",
                                labels=("engine",)),
            "trees": r.counter("gbt_trees_total",
                               "trees fetched to host",
                               labels=("engine",)),
            "forest_chunks": r.counter(
                "gbt_forest_chunks_total",
                "chunks of the device forest a predict or margin replay "
                "asked for: hit (kept on the model from an earlier call) "
                "or built (stacked, padded and put)",
                labels=("engine", "result")),
            "rank_queries": r.counter(
                "gbt_rank_queries_total",
                "queries a ranking handle staged, by the width bucket "
                "their pair sums run in",
                labels=("engine", "bucket")),
            "phase": r.histogram(
                "gbt_phase_seconds",
                "per-phase wall time: bin (host wall of the staging "
                "calls up to their last enqueue, not their completion), "
                "round (boost), warmup (compile), predict (score batch); "
                "with "
                "DMLC_METRICS_GBT_PHASES=1 the external engine adds "
                "hist/split/leaf/apply via block_until_ready",
                labels=("engine", "phase")),
        }
    return _GM


def _host_bin_requested() -> bool:
    """True when ``DMLC_TPU_BIN_BACKEND=cpu`` requests host-side numpy
    binning (unset/empty = bin where the data lives).  Any other value
    is fatal — historically this knob named a jax backend, and silently
    routing e.g. ``tpu`` (or a typo) to the single-core host loop would
    invert the operator's intent.  Host binning uploads the 4×-smaller
    uint8 matrix instead of f32 features, at the price of host-side
    searchsorted."""
    from dmlc_core_tpu.base.parameter import get_env

    backend = get_env("DMLC_TPU_BIN_BACKEND", "", str)
    if backend in ("", "cpu"):
        return backend == "cpu"
    log_fatal(f"DMLC_TPU_BIN_BACKEND={backend!r}: only 'cpu' (host numpy "
              f"binning) or unset (bin on the data's device) are valid")




def _host_bin_t(X: np.ndarray, cuts_np: np.ndarray,
                missing: bool = False) -> np.ndarray:
    """Bin ``X`` on the HOST and return the FEATURE-major bin matrix.

    Pure numpy searchsorted, feature by feature — same semantics as
    :func:`ops.quantile.apply_bins` (bin = #cuts ≤ value, side='right';
    uint8 when bins fit; ``missing=True`` sends NaN to the reserved top
    bin like ``apply_bins_missing``).  Measured 22 s for 10M×28 on one
    core (r4), replacing the earlier jax-CPU-backend detour, and the
    per-feature loop never materializes a second full-matrix copy.
    The device's count bins 24M×28 in 0.25 s of chip time (PERF.md §6,
    PR 28): this path pays only where float32 rows cannot cross to the
    device (NaN over a process-spanning mesh) or H2D bytes are the
    bottleneck."""
    miss_bin = cuts_np.shape[1] + 1
    n_max = miss_bin if missing else cuts_np.shape[1]
    dtype = np.uint8 if n_max < 256 else np.int32
    out = np.empty((X.shape[1], len(X)), dtype)
    for j in range(X.shape[1]):
        col = np.searchsorted(cuts_np[j], X[:, j],
                              side="right").astype(dtype)
        if missing:
            col[np.isnan(X[:, j])] = miss_bin
        out[j] = col
    return out


def _soft_threshold(G, alpha: float):
    """XGBoost's ThresholdL1: shrink the gradient sum toward 0 by the
    L1 penalty before forming weights/gains."""
    return jnp.sign(G) * jnp.maximum(jnp.abs(G) - alpha, 0.0)


def _maybe_l1(G, alpha: float):
    """The shared alpha gate for LEAF-weight sites: thresholded gradient
    sum when L1 is on, the raw sum (identical trace) when off.  The
    split chooser's gain keeps its own gate because its alpha=0 branch
    must preserve the exact ``G**2`` primitive of the pre-alpha trace."""
    return _soft_threshold(G, alpha) if alpha > 0.0 else G


def _make_best_split(B: int, lam: float, gamma: float, mcw: float,
                     with_child_sums: bool = False,
                     mono: Optional[np.ndarray] = None,
                     missing: bool = False, alpha: float = 0.0,
                     cat_bins: Optional[Sequence[int]] = None,
                     max_cat_to_onehot: int = 4,
                     max_cat_threshold: int = 64):
    """Greedy per-node split chooser over a gradient histogram.

    hist [2,N,F,B] → (feat [N], thr [N], split_gain [N]); degenerate
    split (feat 0, thr B-1 → everyone left, gain 0) when gain ≤ gamma.
    Shared by the in-core shard_map round and the external-memory page
    loop.

    ``mono`` ([F] ints ∈ {-1, 0, +1}) enables monotone constraints: a
    candidate split on a constrained feature whose (bound-clipped)
    optimal child weights violate the required ordering gets gain −inf;
    the caller passes each node's inherited weight ``bounds`` [N, 2] and
    propagates them down (see ``grow_tree``), which together with leaf
    clipping makes the trained function globally monotone.

    ``with_child_sums=True`` additionally returns the children's
    ``(g_sum, h_sum)`` as ``[2N]`` arrays (leaf order: left=2i,
    right=2i+1) after the gain.  The cumsum evaluated at the chosen threshold IS the
    left child's sum and parent − left the right's, so at the deepest
    level the leaf g/h sums come for free from the histogram — no extra
    pass over the rows (which an MXU-hostile ``[2,R]·[R,n_leaf]`` scan
    previously spent ~99% of round time on).

    Precision note: on TPU the histogram multiplies g/h by the one-hots
    in bf16 (f32 accumulation), so leaf sums carry ~1e-3 relative
    rounding per entry rather than being bit-identical to the CPU
    segment-sum path.  Split selection always had this property (gain is
    computed from the same histogram); extending it to leaf weights is
    the deliberate price of eliminating the dominant per-round pass.

    ``missing=True`` (XGBoost's learned default direction; exclusive
    with ``mono``, CHECKed at fit): bin ``B-1`` is reserved for NaN
    rows (``apply_bins_missing``), value bins are ``0..B-2``.  Every
    candidate threshold's gain is evaluated with the node's missing
    mass on the left AND the right (the missing-right branch is
    numerically the plain formula — value cumsums exclude bin B-1,
    totals include it, so NaN-free nodes reduce exactly to the
    unconstrained scan), and the better direction is recorded per node
    as ``dir`` (1 = missing left), returned between thr and gain.
    Degenerate nodes keep thr = B-1 / dir = 1: every row, missing
    included, goes left.

    ``cat_bins`` ([F] ints, ``c_f`` > 0 for a CATEGORICAL feature: the
    bins ``0..c_f-1`` of its category→bin table that hold a training
    row, ``ops.quantile.cat_tables``'s ``used``; 0 for a numeric one;
    exclusive with ``mono`` and ``missing``) makes such a feature's
    split a PARTITION of its bins (LightGBM's and XGBoost's rule, Fisher
    1958): per node the ``c_f`` bins are ordered by ``G_k / (H_k +
    lambda)``, ascending and stable (a bin that is empty in the node
    sits at 0 among the others, ties by bin id), and the candidates are
    the ``c_f - 1`` cuts of that order that leave at most
    ``max_cat_threshold`` bins on one side — a prefix of 1..T bins from
    either end; the SET is that side, and it goes LEFT.  A feature of
    ``c_f <= max_cat_to_onehot`` bins offers each single bin against the
    rest instead.  Candidate ``j`` of a categorical feature stands where
    threshold ``j`` of a numeric one does — the gain formula, ``gamma``,
    ``min_child_weight``, ``reg_alpha``, the feature mask and the ONE
    ``argmax`` over ``[F x (B-1)]`` (ties to the lower flat index) are
    shared, with its left sums the set's.  ``best_split`` then returns,
    after ``thr``, the chosen split's left set ``[N, B]`` (bool) for
    EVERY node — a numeric split's is its bins ``<= thr``, a degenerate
    node's all bins — and a categorical node's ``thr`` is its set's
    size less one (never ``B-1``: that marks the degenerate node).  Its
    ``scope=`` keyword names the device scope of the sort and the scan
    (``dmlc.round.L<d>.split.cat``).
    """
    CHECK(mono is None or not missing,
          "monotone constraints are not supported with missing=True "
          "(the constrained-gain branch has no missing-direction form)")
    cat = cat_bins is not None and any(int(c) > 0 for c in cat_bins)
    if cat:
        CHECK(mono is None and not missing,
              "categorical features have no constrained or "
              "missing-direction scan")
        c_all = np.asarray([int(c) for c in cat_bins], np.int64)
        cat_idx = np.flatnonzero(c_all > 0)
        c_f = c_all[cat_idx][:, None]                # [Fc, 1]
        T = int(max_cat_threshold)
        slot = np.arange(B - 1)[None, :]             # candidate j: m = j+1
        one_hot = np.broadcast_to(c_f <= max_cat_to_onehot,
                                  (len(cat_idx), B - 1))
        low = np.broadcast_to(slot + 1 <= T, one_hot.shape)
        valid_cat = np.where(
            one_hot, (slot < c_f) & (c_f >= 2),
            (slot + 1 <= c_f - 1) & (low | (c_f - (slot + 1) <= T)))
        valid_all = np.ones((len(c_all), B - 1), bool)
        valid_all[cat_idx] = valid_cat
        # per feature, for the chosen split: its place among the
        # categorical ones, its bins, whether it offers single bins
        cat_pos = np.zeros(len(c_all), np.int32)
        cat_pos[cat_idx] = np.arange(len(cat_idx))
        used_bins = np.arange(B)[None, :] < c_f      # [Fc, B]

    def cat_left_sums(g, h):
        """Per node and categorical feature the bins' order and, at
        candidate ``j``, the left SET's sums (slot ``B-1``: the node's)."""
        gc, hc = g[:, cat_idx], h[:, cat_idx]        # [N, Fc, B]
        key = jnp.where(used_bins[None], gc / (hc + lam), jnp.inf)
        order = jnp.argsort(key, axis=-1, stable=True)

        def set_sums(v):
            vs = jnp.take_along_axis(v, order, axis=-1)
            cs = jnp.cumsum(vs, axis=-1)
            tot = cs[..., -1:]
            left = jnp.where(one_hot[None], vs[..., :-1],
                             jnp.where(low[None], cs[..., :-1],
                                       tot - cs[..., :-1]))
            return jnp.concatenate([left, tot], axis=-1)

        return order, set_sums(gc), set_sums(hc)

    def cat_left_set(order, feat, thr, split_ok):
        """The chosen split's left set [N, B] and its recorded ``thr``."""
        pos = jnp.asarray(cat_pos)[feat]             # [N]
        is_cat = jnp.asarray(c_all > 0)[feat] & split_ok
        c_n = jnp.asarray(c_all.astype(np.int32))[feat][:, None]
        hot = c_n <= max_cat_to_onehot
        place = jnp.arange(B, dtype=jnp.int32)[None, :]
        j = thr[:, None]
        in_set = jnp.where(hot, place == j,
                           jnp.where(j + 1 <= T, place <= j,
                                     (place > j) & (place < c_n)))
        order_n = jnp.take_along_axis(order, pos[:, None, None],
                                      axis=1)[:, 0]  # [N, B]
        member = jnp.zeros(in_set.shape, bool).at[
            jnp.arange(in_set.shape[0])[:, None], order_n].set(in_set)
        size = jnp.sum(member, axis=1, dtype=jnp.int32)
        return (jnp.where(is_cat[:, None], member, place <= thr[:, None]),
                jnp.where(is_cat, size - 1, thr))

    def best_split(hist, feat_mask=None, bounds=None,
                   scope=contextlib.nullcontext):
        g = hist[0]
        h = hist[1]
        cg = jnp.cumsum(g, axis=-1)                  # [N,F,B] left-incl. sums
        ch = jnp.cumsum(h, axis=-1)
        if cat:
            with scope():
                order, cg_cat, ch_cat = cat_left_sums(g, h)
                cg = cg.at[:, cat_idx].set(cg_cat)
                ch = ch.at[:, cat_idx].set(ch_cat)
        gl = cg[..., :-1]                            # [N,F,B-1] left: bin ≤ b
        hl = ch[..., :-1]
        gt = cg[..., -1:]                            # [N,F,1]
        ht = ch[..., -1:]
        if alpha > 0.0:
            # XGBoost alpha: gain term T(G)²/(H+λ) with the
            # soft-thresholded gradient sum (gated so alpha=0 keeps the
            # exact pre-alpha trace)
            def _score(G, H):
                t = _soft_threshold(G, alpha)
                return t * t / (H + lam)
        else:
            def _score(G, H):
                return G**2 / (H + lam)
        dir_l = None
        if missing:
            miss_g = g[..., B - 1]                   # [N,F] NaN-bin mass
            miss_h = h[..., B - 1]

            def side_gain(gl_, hl_):
                gr_ = gt - gl_
                hr_ = ht - hl_
                gn = (_score(gl_, hl_) + _score(gr_, hr_)
                      - _score(gt, ht))
                ok_ = (hl_ >= mcw) & (hr_ >= mcw)
                return jnp.where(ok_, gn, -jnp.inf)

            gain_r = side_gain(gl, hl)               # missing → right
            gain_l = side_gain(gl + miss_g[..., None],
                               hl + miss_h[..., None])
            gain = jnp.maximum(gain_r, gain_l)
            dir_l = gain_l > gain_r                  # [N,F,B-1] bool
        else:
            gr = gt - gl
            hr = ht - hl
            gain = (_score(gl, hl) + _score(gr, hr) - _score(gt, ht))
        if mono is not None:
            # bounds bind the REALIZABLE child weights, so gain must be
            # evaluated at the clipped weights (XGBoost's constrained
            # gain) — the closed form above assumes unclipped optima and
            # would rank clipped splits by value they cannot achieve.
            # For (-inf, inf) bounds this reduces exactly to the closed
            # form: obj(w*) = -G²/2(H+λ), gain = 2·Δobj.
            wl = -gl / (hl + lam)                    # candidate child weights
            wr = -gr / (hr + lam)
            wp = -gt / (ht + lam)
            if bounds is not None:                   # inherited node bounds
                lo = bounds[:, 0][:, None, None]
                hi = bounds[:, 1][:, None, None]
                wl = jnp.clip(wl, lo, hi)
                wr = jnp.clip(wr, lo, hi)
                wp = jnp.clip(wp, lo, hi)

            def objv(G, H, w):
                return G * w + 0.5 * (H + lam) * w * w

            gain = 2.0 * (objv(gt, ht, wp) - objv(gl, hl, wl)
                          - objv(gr, hr, wr))
            m = jnp.asarray(mono)[None, :, None]     # [1, F, 1]
            viol = ((m > 0) & (wl > wr)) | ((m < 0) & (wl < wr))
            gain = jnp.where(viol, -jnp.inf, gain)
        if not missing:                  # missing folds mcw per direction
            ok = (hl >= mcw) & (hr >= mcw)
            gain = jnp.where(ok, gain, -jnp.inf)
        if feat_mask is not None:                    # colsample: [F] bool
            gain = jnp.where(feat_mask[None, :, None], gain, -jnp.inf)
        if cat:                          # the cuts a categorical feature has
            gain = jnp.where(valid_all[None], gain, -jnp.inf)
        flat = gain.reshape(gain.shape[0], -1)       # [N, F*(B-1)]
        best = jnp.argmax(flat, axis=1)
        best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
        feat = (best // (B - 1)).astype(jnp.int32)
        thr = (best % (B - 1)).astype(jnp.int32)
        split_ok = 0.5 * best_gain > gamma
        feat = jnp.where(split_ok, feat, 0)
        thr = jnp.where(split_ok, thr, B - 1)        # bins ≤ B-1 → all left
        if missing:
            dirv = jnp.take_along_axis(
                dir_l.reshape(dir_l.shape[0], -1), best[:, None],
                axis=1)[:, 0].astype(jnp.int32)
            dirv = jnp.where(split_ok, dirv, 1)      # degenerate: all left
        # XGBoost's reported split gain (0 for degenerate nodes) — kept in
        # the tree arrays so importance_type="gain" costs nothing extra
        split_gain = jnp.where(split_ok, 0.5 * best_gain, 0.0)
        slot_thr = thr                   # (a set's sums stand at its slot)
        if cat:
            with scope():
                left_set, thr = cat_left_set(order, feat, slot_thr, split_ok)
            if not with_child_sums:
                return feat, thr, left_set, split_gain
        if not with_child_sums:
            return ((feat, thr, dirv, split_gain) if missing
                    else (feat, thr, split_gain))
        N, F = g.shape[0], g.shape[1]
        n_idx = jnp.arange(N, dtype=jnp.int32)
        flat_idx = (n_idx * F + feat) * B + slot_thr
        lg = cg.reshape(-1)[flat_idx]                # left-child sums [N]
        lh = ch.reshape(-1)[flat_idx]
        if missing:
            mg = miss_g.reshape(-1)[n_idx * F + feat]
            mh = miss_h.reshape(-1)[n_idx * F + feat]
            # degenerate thr = B-1 already includes the missing bin in
            # its cumsum; adding mg again would double-count it
            add_miss = (dirv == 1) & (thr < B - 1)
            lg = lg + jnp.where(add_miss, mg, 0.0)
            lh = lh + jnp.where(add_miss, mh, 0.0)
        tg = cg[:, 0, -1]                            # node totals (any feature)
        th_ = ch[:, 0, -1]
        child_g = jnp.stack([lg, tg - lg], axis=1).reshape(2 * N)
        child_h = jnp.stack([lh, th_ - lh], axis=1).reshape(2 * N)
        if missing:
            return feat, thr, dirv, split_gain, child_g, child_h
        if cat:
            return feat, thr, left_set, split_gain, child_g, child_h
        return feat, thr, split_gain, child_g, child_h

    return best_split


# -- external-memory page kernels (jitted once per page shape) --------------

@jax.jit
def _advance_node(bins_t, node, feat, thr):
    """Route rows one level down the tree; padding rows (node<0) stay -1.
    ``bins_t`` is feature-major [F, n]; the selected feature's bin comes
    from ops.select_feature_bins (shared gather-free select)."""
    valid = node >= 0
    safe = jnp.where(valid, node, 0)
    row_bin = select_feature_bins(bins_t, feat[safe])
    nxt = 2 * safe + (row_bin > thr[safe]).astype(jnp.int32)
    return jnp.where(valid, nxt, -1)


@partial(jax.jit, static_argnums=(3,))
def _leaf_sums(node, g, h, n_leaf):
    safe = jnp.where(node >= 0, node, 0)  # padding rows carry g=h=0
    return (jax.ops.segment_sum(g, safe, num_segments=n_leaf),
            jax.ops.segment_sum(h, safe, num_segments=n_leaf))


