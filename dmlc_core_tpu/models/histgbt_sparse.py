"""Sparse high-dimensional hist-GBT (LibSVM's natural workloads).

``HistGBT`` densifies to an ``[n, F]`` bin matrix — right for HIGGS /
Criteo-39, impossible for bag-of-words / hashed one-hot data
(F ≈ 10⁴–10⁶, density < 1%).  :class:`SparseHistGBT` is the
sparsity-aware engine over ``ops/sparse_hist.py``'s ragged flat bin
space (SURVEY.md §7 hard part (a); BASELINE config 3 "sparse CSR";
XGBoost's sparsity-aware split finding):

* histograms are ONE ``segment_sum`` over present entries per level —
  O(nnz), never O(n·F);
* per-feature bin counts adapt to distinct values (a binary indicator
  costs 2 bins, not 256), so total bins track data content, not F×256;
* absent entries ARE the missing mass: every split evaluates the
  node's absent g/h (``total − present``) on both sides and records the
  better default direction — the same learned-direction semantics as
  the dense NaN engine (``absent ≡ NaN``), tested against a brute-force
  oracle tree grower.

Trees store (feat, thr, dir, leaf) per level like the dense missing
engine; ``thr`` is a LOCAL bin index into the feature's ragged cut
range.  Distributed data-parallel fits shard rows across workers:
global cuts via the candidate-matrix allgather-merge and per-level
histogram/total ``allreduce_device`` (see :meth:`SparseHistGBT.fit`).
v1 scope (recorded in PARITY.md): objectives binary:logistic /
reg:squarederror, unweighted quantile cuts.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from dmlc_core_tpu.base import metrics as _metrics
from dmlc_core_tpu.base.logging import CHECK, CHECK_EQ, LOG
from dmlc_core_tpu.base.parameter import get_env
from dmlc_core_tpu.base.timer import get_time
from dmlc_core_tpu.models.gbt_objectives import (OBJECTIVES,
                                                 fold_scale_pos_weight)
from dmlc_core_tpu.models.gbt_split import _maybe_l1, gbt_metrics
from dmlc_core_tpu.models.histgbt import HistGBTParam
from dmlc_core_tpu.ops.sparse_hist import (SparseCuts, bin_sparse_entries,
                                           build_sparse_cuts, csr_rows,
                                           level_histogram,
                                           merge_sparse_cut_candidates,
                                           node_totals, route_level,
                                           sparse_best_split,
                                           sparse_cut_candidates)

__all__ = ["SparseHistGBT"]


@jax.jit
def _leaf_update(preds, node, leaf):
    return preds + leaf[jnp.clip(node, 0, leaf.shape[0] - 1)]


@partial(jax.jit, static_argnames=("depth",))
def _predict_sparse(margin, row_e, gb_e, feats, thrs, dirs, leafs,
                    bin_ptr_d, feat_of_bin_d, *, depth: int):
    """Whole-ensemble sparse scoring as ONE dispatch: ``lax.scan`` over
    the stacked trees, levels unrolled (static shapes throughout)."""
    def body(m, tree):
        f, t, d, lf = tree
        node = jnp.zeros(m.shape[0], jnp.int32)
        for level in range(depth):
            nn = 1 << level
            node = route_level(row_e, gb_e, node, f[level, :nn],
                               t[level, :nn], d[level, :nn],
                               bin_ptr_d, feat_of_bin_d)
        return m + lf[jnp.clip(node, 0, lf.shape[0] - 1)], None
    out, _ = jax.lax.scan(body, margin, (feats, thrs, dirs, leafs))
    return out


def _pack_tree(feats, thrs, dirs, gains, leaf, *, half):
    """One flat f32 array per tree → ONE host fetch.  Every separate
    ``np.asarray`` is a device round trip; depth×4 of them per round
    paid per-fetch latency while the kernels were sub-ms."""
    def cat(parts, dtype=jnp.float32):
        return jnp.concatenate([
            jnp.pad(p.astype(dtype), (0, half - p.shape[0]))
            for p in parts])
    return jnp.concatenate([cat(feats), cat(thrs), cat(dirs),
                            cat(gains), leaf])


@partial(jax.jit,
         static_argnames=("k", "obj", "depth", "total_bins", "n_dense",
                          "b_max", "lam", "gamma", "mcw", "alpha", "eta"))
def _sparse_rounds_k(row_e, gb_e, y, w, preds, bin_ptr_d, feat_of_bin_d,
                     last_mask, dense_pos_d, *, k: int, obj, depth: int,
                     total_bins: int, n_dense: int, b_max: int,
                     lam: float, gamma: float,
                     mcw: float, alpha: float, eta: float):
    """``k`` boosting rounds in ONE dispatch (``lax.scan``), returning
    the updated margins and the ``[k, L]`` packed trees — the sparse
    analogue of the dense engine's rounds-per-dispatch chunking.
    Chunking amortizes the per-dispatch + per-fetch latency k×."""
    def body(preds_c, _):
        g, h = obj.grad_hess(preds_c, y)
        flat, node, leaf = _sparse_round_core(
            row_e, gb_e, g * w, h * w, bin_ptr_d, feat_of_bin_d,
            last_mask, dense_pos_d, depth=depth,
            total_bins=total_bins, n_dense=n_dense, b_max=b_max,
            lam=lam, gamma=gamma, mcw=mcw, alpha=alpha, eta=eta)
        return _leaf_update(preds_c, node, leaf), flat

    preds, flats = jax.lax.scan(body, preds, None, length=k)
    return preds, flats


@partial(jax.jit,
         static_argnames=("depth", "total_bins", "n_dense", "b_max",
                          "lam", "gamma", "mcw", "alpha", "eta"))
def _sparse_round(row_e, gb_e, g, h, bin_ptr_d, feat_of_bin_d, last_mask,
                  dense_pos_d, *, depth: int, total_bins: int,
                  n_dense: int, b_max: int, lam: float,
                  gamma: float, mcw: float, alpha: float, eta: float):
    """ONE dispatch per boosting round: all levels (route → histogram →
    totals → split) unrolled in a single program (the per-round entry
    used when per-round host RNG must interleave, i.e. subsample)."""
    return _sparse_round_core(row_e, gb_e, g, h, bin_ptr_d,
                              feat_of_bin_d, last_mask, dense_pos_d,
                              depth=depth, total_bins=total_bins,
                              n_dense=n_dense, b_max=b_max, lam=lam,
                              gamma=gamma, mcw=mcw, alpha=alpha, eta=eta)


def _sparse_round_core(row_e, gb_e, g, h, bin_ptr_d, feat_of_bin_d,
                       last_mask, dense_pos_d, *, depth: int,
                       total_bins: int, n_dense: int, b_max: int,
                       lam: float, gamma: float, mcw: float,
                       alpha: float, eta: float, reduce_fn=None):
    # reduce_fn: cross-worker sum hook (allreduce_device) applied to
    # every histogram / node-total — identity single-worker, so the
    # local and distributed engines share ONE tree-growing core
    rf = reduce_fn or (lambda x: x)
    n = g.shape[0]
    n_leaf = 1 << depth
    half = max(n_leaf >> 1, 1)
    node = jnp.zeros(n, jnp.int32)
    feats, thrs, dirs, gains = [], [], [], []
    prev_full = None
    feat = thr = dirv = None
    for level in range(depth):
        n_nodes = 1 << level
        n_build = 1 if level == 0 else n_nodes >> 1
        if level > 0:
            node = route_level(row_e, gb_e, node, feat, thr, dirv,
                               bin_ptr_d, feat_of_bin_d)
        left = rf(level_histogram(row_e, gb_e, node, g, h,
                                  n_build=n_build,
                                  total_bins=total_bins, level=level))
        if level == 0:
            full = left
        else:
            full = jnp.stack([left, prev_full - left],
                             axis=2).reshape(2, n_nodes, total_bins)
        prev_full = full
        totals = rf(node_totals(node, g, h, n_nodes=n_nodes))
        feat, thr, dirv, gain = sparse_best_split(
            full, totals, bin_ptr_d, feat_of_bin_d, last_mask,
            dense_pos_d, n_dense=n_dense, b_max=b_max,
            lam=lam, gamma=gamma, mcw=mcw, alpha=alpha)
        feats.append(feat)
        thrs.append(thr)
        dirs.append(dirv)
        gains.append(gain)
    node = route_level(row_e, gb_e, node, feat, thr, dirv,
                       bin_ptr_d, feat_of_bin_d)
    lt = rf(node_totals(node, g, h, n_nodes=n_leaf))
    leaf = (-_maybe_l1(lt[0], alpha) / (lt[1] + lam)
            * eta).astype(jnp.float32)
    return _pack_tree(feats, thrs, dirs, gains, leaf, half=half), node, leaf


class SparseHistGBT:
    """Sparsity-aware boosting over CSR input.

    :meth:`fit`/:meth:`predict` take raw ``offset/index/value`` arrays;
    :meth:`fit_block`/:meth:`predict_block` accept a
    :class:`~dmlc_core_tpu.data.row_block.RowBlock` directly (the data
    plane's parser output)."""

    _MODEL_MAGIC = b"DCTSGB01"

    def __init__(self, param: Optional[HistGBTParam] = None,
                 **kwargs: Any):
        self.param = param or HistGBTParam()
        if kwargs:
            self.param.init(kwargs)
        p = self.param
        CHECK(p.objective in ("binary:logistic", "reg:squarederror"),
              f"SparseHistGBT supports binary:logistic/reg:squarederror "
              f"(got {p.objective!r}); use HistGBT for the rest")
        CHECK(not (p.monotone_constraints
                   and any(int(v) for v in p.monotone_constraints)),
              "SparseHistGBT: monotone constraints not supported")
        CHECK("c" not in p.feature_types,
              "SparseHistGBT: categorical features (feature_types) not "
              "supported — its splits are thresholds on the CSR cuts; "
              "use HistGBT")
        CHECK(p.colsample_bytree >= 1.0,
              "SparseHistGBT: colsample_bytree not supported (v1) — "
              "a silently ignored knob would train a different model")
        CHECK(p.grow_policy == "depthwise" and p.max_depth >= 1,
              "SparseHistGBT grows depth-wise trees only "
              "(grow_policy='lossguide' is HistGBT's)")
        # the field bound is inclusive; 0.0 would silently train
        # all-degenerate trees (same guard as the dense engine)
        CHECK(p.subsample > 0.0, "subsample must be > 0")
        self._obj = OBJECTIVES[p.objective]
        self.cuts: Optional[SparseCuts] = None
        self.n_features: int = 0
        self.trees: List[Dict[str, np.ndarray]] = []
        self.last_fit_seconds: Optional[float] = None

    # -- input plumbing -------------------------------------------------
    @staticmethod
    def _csr(offset, index, value):
        offset = np.ascontiguousarray(offset, np.int64)
        index = np.ascontiguousarray(index, np.int64)
        value = (np.ones(len(index), np.float32) if value is None
                 else np.ascontiguousarray(value, np.float32))
        CHECK_EQ(len(index), len(value), "index/value length mismatch")
        CHECK_EQ(int(offset[-1]), len(index), "offset[-1] != nnz")
        CHECK(len(index) == 0 or int(index.min()) >= 0,
              "negative feature indices — they would wrap through "
              "numpy indexing into the LAST feature's bins and score "
              "silently wrong")
        CHECK(np.isfinite(value).all(),
              "sparse values must be finite — absent entries ARE the "
              "missing mass; an explicit NaN would silently bin as the "
              "feature's largest value, not route by the learned "
              "direction")
        # the routing kernel relies on at most ONE entry per
        # (row, feature): duplicates would sum their side verdicts and
        # route the row to an invalid node id, silently corrupting
        # every later tree.  One lexsort over nnz, done per call.
        if len(index):
            rows = csr_rows(offset)
            order = np.lexsort((index, rows))
            dup = ((rows[order][1:] == rows[order][:-1])
                   & (index[order][1:] == index[order][:-1]))
            CHECK(not dup.any(),
                  "duplicate (row, feature) entries in CSR input — "
                  "sum or drop duplicates first")
        return offset, index, value

    # -- training -------------------------------------------------------
    def fit(self, offset, index, value, y,
            weight: Optional[np.ndarray] = None,
            n_features: Optional[int] = None,
            cuts: Optional[SparseCuts] = None,
            distributed: Optional[bool] = None) -> "SparseHistGBT":
        """Boost ``n_trees`` rounds over CSR rows.

        ``n_features`` pins the feature-space width (else
        ``max(index)+1``) — pass it when shards/batches may not touch
        the top feature id.  ``cuts`` injects precomputed ragged cuts
        (else built from this input; distributed fits merge every
        worker's candidates).

        **Distributed** (auto when ``coll.world_size() > 1`` via the
        DMLC env ABI; ``distributed=False`` forces a process-local fit
        inside a cluster — e.g. a per-worker comparator): each worker
        holds its OWN row shard; the candidate matrix
        allgather merges global cuts, and per-level histograms / node
        totals allreduce across workers (``allreduce_device``), so all
        workers grow identical trees — the sparse engine's rabit-
        allreduce replacement.  Runs the per-level host loop (the
        collectives must interleave with the level kernels), so it
        trades the fused-round dispatch amortization for scale-out.
        """
        from dmlc_core_tpu.base import compile_cache as _cc
        from dmlc_core_tpu.parallel import collectives as coll

        # persistent compile cache: a serve restart or repeat process
        # re-reads this engine's programs instead of recompiling
        _cc.configure()
        p = self.param
        offset, index, value = self._csr(offset, index, value)
        y = np.ascontiguousarray(y, np.float32)
        n = len(offset) - 1
        CHECK_EQ(len(y), n, "y/offset row mismatch")
        weight = fold_scale_pos_weight(p, y, weight)  # spw ≡ inst weight
        F = int(n_features or (index.max() + 1 if len(index) else 1))
        if distributed is None:
            distributed = coll.world_size() > 1
        if distributed:
            # sparse shards can disagree on the max feature id; cuts,
            # bins and histograms need ONE global F
            F = int(coll.allreduce(np.asarray([F], np.int64),
                                   op="max")[0])
        CHECK(len(index) == 0 or int(index.max()) < F,
              "n_features smaller than max feature index")
        CHECK(F <= 1 << 24,
              "n_features > 2^24: the packed-tree fetch rides f32 "
              "(exact only to 16,777,216) — split feature ids beyond "
              "that would silently corrupt.  Hash into <= 2^24 buckets")
        self.n_features = F

        t0 = get_time()
        if cuts is not None:
            CHECK_EQ(cuts.n_features, F,
                     "injected cuts' feature count != n_features")
            self.cuts = cuts
        elif distributed:
            msg_mb = F * (p.n_bins - 1) * 4 >> 20
            if msg_mb > 256:
                LOG("WARNING", "distributed sparse cuts: the [F, "
                    "n_bins-1] candidate allgather is %d MB/worker at "
                    "F=%d — drop n_bins (sparse features rarely need "
                    "256) or precompute cuts= once and inject them",
                    msg_mb, F)
            cand = sparse_cut_candidates(index, value, F, p.n_bins)
            gathered = np.asarray(coll.allgather(cand))   # [W, F, nb]
            self.cuts = merge_sparse_cut_candidates(gathered)
        else:
            self.cuts = build_sparse_cuts(index, value, F, p.n_bins)
        TB = self.cuts.total_bins
        LOG("INFO", "SparseHistGBT: %d rows x %d features, %d nnz "
            "(density %.4f), %d ragged bins (dense would be %d)",
            n, F, len(index), len(index) / max(n * F, 1), TB,
            F * p.n_bins)

        bin_ptr_d = jnp.asarray(self.cuts.bin_ptr)
        feat_of_bin_d = jnp.asarray(self.cuts.feat_of_bin)
        # each feature's LAST bin is not a threshold candidate
        last_mask = jnp.asarray(
            np.isin(np.arange(TB), self.cuts.bin_ptr[1:] - 1))
        # padded-dense slot per global bin — the split scan's exact
        # per-feature cumsum layout (see sparse_best_split numerics)
        widths = np.diff(self.cuts.bin_ptr)
        b_max = int(widths.max()) if len(widths) else 1
        dense_pos = (self.cuts.feat_of_bin.astype(np.int64) * b_max
                     + np.arange(TB)
                     - self.cuts.bin_ptr[self.cuts.feat_of_bin])
        dense_pos_d = jnp.asarray(dense_pos)
        n_dense = F * b_max
        # one wide feature pads EVERY narrow one: the split scan's
        # per-level scatter buffer is O(nodes * n_dense) f32 — the
        # dense-size blow-up this engine exists to avoid.  Same spirit
        # as the distributed-cuts allgather warning above.
        if n_dense > 16 * max(TB, 1):
            LOG("WARNING", "SparseHistGBT: padded-dense split buffer has "
                "%d slots for only %d real bins (widest feature: "
                "b_max=%d bins) — one high-cardinality feature is "
                "padding every narrow one; drop n_bins (wide sparse "
                "features rarely need %d bins) or bin that feature "
                "coarser via precomputed cuts=", n_dense, TB, b_max,
                p.n_bins)
        y_d = jnp.asarray(y)
        w_d = (jnp.ones(n, jnp.float32) if weight is None
               else jnp.asarray(np.asarray(weight, np.float32)))
        preds = jnp.full(n, p.base_score, jnp.float32)

        depth = p.max_depth
        n_leaf = 1 << depth
        half = max(n_leaf >> 1, 1)
        d = depth * half
        self.trees = []
        cfg = dict(depth=depth, total_bins=TB, n_dense=n_dense,
                   b_max=b_max, lam=p.reg_lambda,
                   gamma=p.gamma, mcw=p.min_child_weight,
                   alpha=p.reg_alpha, eta=p.learning_rate)

        # cold-start overlap (doc/performance.md): every static of the
        # round program is pinned the moment the cuts exist, but the
        # heavy host pass — bin_sparse_entries searchsorting every nnz
        # entry — hasn't run yet.  AOT-compile the K-round program on a
        # background worker while that binning runs; join before the
        # boosting loop (a refused compile raises there).  A
        # multi-worker job and a subsampled fit stay serial.
        self.last_compile_seconds = None
        warm_bg = warm_exec = None
        warm_k = min(int(get_env("DMLC_TPU_SPARSE_ROUNDS_PER_DISPATCH",
                                 8, int)), p.n_trees)
        if not distributed and p.subsample >= 1.0 and warm_k > 0:
            nnz = len(index)
            obj = self._obj

            def _compile_rounds():
                args = (jax.ShapeDtypeStruct((nnz,), jnp.int32),
                        jax.ShapeDtypeStruct((nnz,), jnp.int32),
                        y_d, w_d, preds, bin_ptr_d, feat_of_bin_d,
                        last_mask, dense_pos_d)
                return _sparse_rounds_k.lower(
                    *args, k=warm_k, obj=obj, **cfg).compile()

            warm_bg = _cc.BackgroundCompiler(
                {"rounds_k": _compile_rounds}, what="sparse_round")

        gb = bin_sparse_entries(index, value, self.cuts)
        rows = csr_rows(offset)
        row_e = jnp.asarray(rows)
        gb_e = jnp.asarray(gb)
        if warm_bg is not None:
            warm_exec = warm_bg.join().get("rounds_k")
            self.last_compile_seconds = warm_bg.compile_seconds

        def unpack(flat):
            self.trees.append({
                "feat": flat[:d].astype(np.int32).reshape(depth, half),
                "thr": flat[d:2 * d].astype(np.int32).reshape(depth,
                                                              half),
                "dir": flat[2 * d:3 * d].astype(bool).reshape(depth,
                                                              half),
                "gain": flat[3 * d:4 * d].reshape(depth, half),
                "leaf": flat[4 * d:],
            })

        rng = np.random.default_rng(p.seed)
        if distributed:
            preds = self._fit_rounds_distributed(
                row_e, gb_e, y_d, w_d, preds, bin_ptr_d, feat_of_bin_d,
                last_mask, dense_pos_d, cfg, unpack, coll, n)
        elif p.subsample >= 1.0:
            # K rounds per dispatch; the [K, L] packed trees are ONE
            # fetch per chunk
            K = int(get_env("DMLC_TPU_SPARSE_ROUNDS_PER_DISPATCH", 8,
                            int))
            done = 0
            while done < p.n_trees:
                k = min(K, p.n_trees - done)
                dyn = (row_e, gb_e, y_d, w_d, preds, bin_ptr_d,
                       feat_of_bin_d, last_mask, dense_pos_d)
                if warm_exec is not None and k == warm_k:
                    preds, flats = warm_exec(*dyn)
                else:
                    preds, flats = _sparse_rounds_k(
                        *dyn, k=k, obj=self._obj, **cfg)
                for flat in np.asarray(flats):
                    unpack(flat)
                done += k
        else:
            # per-round host RNG draws (reproducible numpy stream)
            for r in range(p.n_trees):
                g, h = self._obj.grad_hess(preds, y_d)
                keep = (rng.random(n) < p.subsample).astype(np.float32)
                wk = w_d * jnp.asarray(keep)
                flat_d, node, leaf = _sparse_round(
                    row_e, gb_e, g * wk, h * wk, bin_ptr_d,
                    feat_of_bin_d, last_mask, dense_pos_d, **cfg)
                preds = _leaf_update(preds, node, leaf)
                unpack(np.asarray(flat_d))
        jax.block_until_ready(preds)
        self.last_fit_seconds = get_time() - t0
        if _metrics.enabled() and p.n_trees:
            m = gbt_metrics()
            m["rounds"].inc(p.n_trees, engine="sparse")
            m["trees"].inc(p.n_trees, engine="sparse")
            m["phase"].observe(self.last_fit_seconds / p.n_trees,
                               engine="sparse", phase="round")
        self._train_margin = preds
        return self

    def _fit_rounds_distributed(self, row_e, gb_e, y_d, w_d, preds,
                                bin_ptr_d, feat_of_bin_d, last_mask,
                                dense_pos_d, cfg, unpack, coll, n):
        """Per-round boosting with cross-worker collectives.

        Runs the SAME tree-growing core as the local engines with
        ``reduce_fn=allreduce_device`` summing every histogram and
        node-total across workers between the level kernels — split
        choices, and therefore trees, are identical on every rank.
        Eager (unjitted) so the collectives interleave; subsample draws
        come from a rank-seeded host RNG (each worker samples its own
        shard, the ext engine's convention)."""
        p = self.param
        rngr = np.random.default_rng([p.seed, coll.rank()])
        for r in range(p.n_trees):
            g, h = self._obj.grad_hess(preds, y_d)
            wk = w_d
            if p.subsample < 1.0:
                keep = (rngr.random(n) < p.subsample).astype(np.float32)
                wk = w_d * jnp.asarray(keep)
            flat, node, leaf = _sparse_round_core(
                row_e, gb_e, g * wk, h * wk, bin_ptr_d, feat_of_bin_d,
                last_mask, dense_pos_d,
                reduce_fn=coll.allreduce_device, **cfg)
            preds = _leaf_update(preds, node, leaf)
            unpack(np.asarray(flat))
        return preds

    def fit_block(self, block, y=None, weight: Optional[np.ndarray] = None,
                  n_features: Optional[int] = None,
                  cuts: Optional[SparseCuts] = None,
                  distributed: Optional[bool] = None) -> "SparseHistGBT":
        """Train from a :class:`RowBlock` (labels/weights from the block
        unless overridden; ``cuts``/``distributed`` forward to
        :meth:`fit`)."""
        return self.fit(block.offset, block.index, block.value,
                        block.label if y is None else y,
                        weight=block.weight if weight is None else weight,
                        n_features=n_features, cuts=cuts,
                        distributed=distributed)

    # -- inference ------------------------------------------------------
    def predict_block(self, block, **kw) -> np.ndarray:
        """Score a :class:`RowBlock` (see :meth:`predict`)."""
        return self.predict(block.offset, block.index, block.value, **kw)

    def predict(self, offset, index, value,
                output_margin: bool = False,
                n_trees: Optional[int] = None) -> np.ndarray:
        """Score CSR rows with the trained ensemble (absent = missing,
        routed by each node's learned direction)."""
        CHECK(self.cuts is not None and self.trees, "predict before fit")
        offset, index, value = self._csr(offset, index, value)
        # entries with feature ids beyond the TRAINING space carry no
        # split information — drop them (they are "absent" to the model)
        known = index < self.n_features
        if not known.all():
            keep_rows = csr_rows(offset)[known]
            index, value = index[known], value[known]
            rows = keep_rows
        else:
            rows = csr_rows(offset)
        gb = bin_sparse_entries(index, value, self.cuts)
        n = len(offset) - 1
        row_e = jnp.asarray(rows)
        gb_e = jnp.asarray(gb)
        bin_ptr_d = jnp.asarray(self.cuts.bin_ptr)
        feat_of_bin_d = jnp.asarray(self.cuts.feat_of_bin)
        margin = jnp.full(n, self.param.base_score, jnp.float32)
        T = len(self.trees) if n_trees is None else min(n_trees,
                                                       len(self.trees))
        depth = self.param.max_depth
        trees = self.trees[:T]
        margin = _predict_sparse(
            margin, row_e, gb_e,
            jnp.asarray(np.stack([t["feat"] for t in trees])),
            jnp.asarray(np.stack([t["thr"] for t in trees])),
            jnp.asarray(np.stack([t["dir"] for t in trees])),
            jnp.asarray(np.stack([t["leaf"] for t in trees])),
            bin_ptr_d, feat_of_bin_d, depth=depth)
        out = np.asarray(margin)
        if output_margin:
            return out
        return np.asarray(self._obj.transform(jnp.asarray(out)))

    # -- introspection --------------------------------------------------
    def feature_importances(self, importance_type: str = "weight"
                            ) -> np.ndarray:
        """Per-feature importance over the ensemble (``"weight"`` =
        count of real splits, ``"gain"`` = total split gain — XGBoost's
        notions).  Degenerate/padding slots carry gain 0 (the split
        chooser writes gain only when it beats gamma), so ``gain > 0``
        identifies genuine splits."""
        CHECK(len(self.trees) > 0, "no trees trained")
        CHECK(importance_type in ("weight", "gain"),
              f"unsupported importance_type {importance_type!r}")
        out = np.zeros(self.n_features,
                       np.float64 if importance_type == "gain"
                       else np.int64)
        for tree in self.trees:
            for level in range(tree["feat"].shape[0]):
                nn = 1 << level
                feat = tree["feat"][level][:nn]
                gain = tree["gain"][level][:nn]
                real = gain > 0
                if importance_type == "weight":
                    out += np.bincount(feat[real],
                                       minlength=self.n_features)
                else:
                    np.add.at(out, feat[real], gain[real])
        return out

    # -- persistence ----------------------------------------------------
    def save_model(self, uri: str) -> None:
        """Params + ragged cuts + trees to any Stream URI."""
        from dmlc_core_tpu.io.serializer import write_obj
        from dmlc_core_tpu.io.stream import Stream

        CHECK(self.cuts is not None and len(self.trees) > 0,
              "save_model before fit")
        s = Stream.create(uri, "w")
        try:
            s.write(self._MODEL_MAGIC)
            write_obj(s, {
                "param": self.param.to_dict(),
                "n_features": self.n_features,
                "cut_vals": self.cuts.cut_vals,
                "cut_ptr": self.cuts.cut_ptr,
                "trees": self.trees,
            })
        finally:
            s.close()

    @classmethod
    def load_model(cls, uri: str) -> "SparseHistGBT":
        from dmlc_core_tpu.io.serializer import read_obj
        from dmlc_core_tpu.io.stream import Stream

        s = Stream.create(uri, "r")
        try:
            magic = s.read(len(cls._MODEL_MAGIC))
            CHECK_EQ(bytes(magic), cls._MODEL_MAGIC,
                     f"not a SparseHistGBT model: {uri}")
            payload = read_obj(s)
        finally:
            s.close()
        model = cls()
        model.param.init(payload["param"])
        model._obj = OBJECTIVES[model.param.objective]
        model.n_features = int(payload["n_features"])
        cut_ptr = np.asarray(payload["cut_ptr"], np.int64)
        widths = np.diff(cut_ptr) + 1
        bin_ptr = np.concatenate([[0], np.cumsum(widths)]).astype(np.int64)
        feat_of_bin = np.repeat(
            np.arange(model.n_features, dtype=np.int32), widths)
        model.cuts = SparseCuts(
            np.asarray(payload["cut_vals"], np.float32), cut_ptr,
            bin_ptr, feat_of_bin)
        model.trees = [dict(t) for t in payload["trees"]]
        return model
