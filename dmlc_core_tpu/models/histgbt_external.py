"""HistGBT external-memory engine (out-of-core boosting).

The ``fit_external`` side of :class:`~dmlc_core_tpu.models.histgbt.HistGBT`
— streaming sketch pass, page binning, and the bounded-device-memory
chunk loop (BASELINE config 3; reference seam: ``disk_row_iter.h``'s
page-cached training loop + rabit's sketch allreduce, SURVEY.md §2b/§7).
Split out of ``histgbt.py`` (round-4 verdict #6): this module owns the
``_ext_*`` jitted round pieces and the :class:`_ExternalMemoryEngine`
mixin that ``HistGBT`` inherits; the in-core shard_map engine stays in
``histgbt.py``.

Module-level jits (config via static args) so jax.jit's cache — keyed on
function identity + statics + shapes — carries compiled programs across
fits and across HistGBT instances; defined as per-fit closures they
recompiled every call (~2·depth+5 programs, seconds each on a 1-core
host).
"""

from __future__ import annotations

import os
from functools import lru_cache, partial
from typing import Any, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from dmlc_core_tpu.base import metrics as _metrics
from dmlc_core_tpu.base.logging import CHECK, LOG
from dmlc_core_tpu.base.timer import block_until_ready_time, get_time
from dmlc_core_tpu.ops.histogram import build_histogram
from dmlc_core_tpu.ops.quantile import apply_bins_t
from dmlc_core_tpu.models.gbt_split import (_advance_node, _host_bin_requested,
                                            _host_bin_t, _leaf_sums,
                                            _make_best_split, _maybe_l1,
                                            gbt_metrics)

__all__ = ["_ExternalMemoryEngine"]


# -- chunked external-memory round pieces -----------------------------------
# Module-level jits (config via static args) so jax.jit's cache — keyed on
# function identity + statics + shapes — carries compiled programs across
# fits and across HistGBT instances; defined as per-fit closures they
# recompiled every call (~2·depth+5 programs, seconds each on a 1-core
# host).

@partial(jax.jit, static_argnames=("obj",))
def _ext_gh(preds, y, wk, *, obj):
    # multiclass margins are class-major [K, n]: the weights broadcast
    g, h = obj.grad_hess(preds, y)
    return g * wk, h * wk


@partial(jax.jit, static_argnames=("level", "col", "B", "method"))
def _ext_adv_hist_lvl(bins, node, g, h, feat_prev, thr_prev, *,
                      level, col, B, method):
    """Advance nodes one level (using the PREVIOUS level's split, level 0
    skips it) then build this level's histogram — fused so a streamed
    chunk's bins upload is consumed ONCE per level, not once for hist and
    again for advance."""
    if level > 0:
        node = _advance_node(bins, node, feat_prev, thr_prev)
    g_c = g if col is None else g[col]
    h_c = h if col is None else h[col]
    n_nodes = 1 << level
    n_build = 1 if level == 0 else n_nodes >> 1
    nd = node
    if level > 0:
        nd = jnp.where((nd >= 0) & (nd % 2 == 0), nd >> 1, -1)
    return node, build_histogram(bins, nd, g_c, h_c, n_build, B,
                                 method, transposed=True)


@partial(jax.jit, static_argnames=("n_leaf",))
def _ext_final_adv_leaf(bins, node, g_c, h_c, feat, thr, *, n_leaf):
    """Last advance (deepest split) fused with the leaf g/h sums — again
    one bins consumption for the level."""
    node = _advance_node(bins, node, feat, thr)
    gs, hs = _leaf_sums(node, g_c, h_c, n_leaf)
    return node, gs, hs


@partial(jax.jit, static_argnames=("level", "B"))
def _ext_sib_stack(hist, prev_hist, *, level, B):
    n_nodes = 1 << level
    return jnp.stack([hist, prev_hist - hist], axis=2).reshape(
        2, n_nodes, hist.shape[2], B)


@lru_cache(maxsize=64)
def _ext_split_fn(B, lam, gamma, mcw, alpha=0.0):
    return jax.jit(_make_best_split(B, lam, gamma, mcw, alpha=alpha))


@partial(jax.jit, static_argnames=("col", "n_leaf"))
def _ext_upd_preds(preds, node, leaf, *, col, n_leaf):
    gain = leaf[jnp.clip(node, 0, n_leaf - 1)]
    if col is None:
        return preds + gain
    return preds.at[col].add(gain)


@partial(jax.jit, static_argnames=("lam", "eta", "alpha"))
def _ext_leaf_calc(gsum, hsum, *, lam, eta, alpha=0.0):
    return (-_maybe_l1(gsum, alpha) / (hsum + lam)
            * eta).astype(jnp.float32)


@partial(jax.jit, static_argnames=("half",))
def _ext_pack_tree(feats, thrs, gains, leaf, *, half):
    """One flat f32 array per tree → ONE host fetch (feat/thr are small
    ints, exact in f32)."""
    fp = jnp.concatenate([jnp.pad(f, (0, half - f.shape[0]))
                          for f in feats]).astype(jnp.float32)
    tp = jnp.concatenate([jnp.pad(t, (0, half - t.shape[0]))
                          for t in thrs]).astype(jnp.float32)
    gp = jnp.concatenate([jnp.pad(g, (0, half - g.shape[0]))
                          for g in gains])
    return jnp.concatenate([fp, tp, gp, leaf])


@partial(jax.jit, static_argnames=("nv", "obj"))
def _ext_eval_loss(preds, y, *, nv, obj):
    return jnp.sum(obj.row_loss(preds[..., :nv], y[:nv]))


@lru_cache(maxsize=256)
def _ext_const_fn(shape, fill, dtype_name):
    """Cached jitted constant-fill (init margins / zero node vectors);
    shape-keyed and bounded like :func:`_init_margin_fn`."""
    dtype = np.dtype(dtype_name)
    return jax.jit(lambda: jnp.full(shape, fill, dtype))




class _ExternalMemoryEngine:
    """External-memory (out-of-core) training methods mixed into
    :class:`~dmlc_core_tpu.models.histgbt.HistGBT`.  Relies on the
    host class for param/mesh/objective plumbing and the in-core
    ``_boost_binned`` engine (the device-cached route).
    """

    def fit_external(
        self,
        row_iter,
        num_col: Optional[int] = None,
        eval_every: int = 0,
        sketch_pages: int = 32,
        cuts: Optional[jax.Array] = None,
        cache_device: bool = False,
        warmup_rounds: int = 0,
    ) -> "HistGBT":
        """Out-of-core boosting over a :class:`RowBlockIter` (sparse CSR
        pages from a Parser/DiskRowIter — the Criteo-scale path).

        Never materializes the dataset: pass 1 streams pages through a
        bounded-memory :class:`SketchAccumulator` (the fixed-size sketch
        "allreduce" replacing the reference world's variable-size rabit
        sketch merge); pass 2 bins each page to uint8 (4× smaller than
        raw f32, the only per-row state kept); each round then rescans
        binned pages level-by-level, accumulating node histograms on
        device and allreducing across workers.  Missing CSR entries bin
        as 0.0 (XGBoost's dense-hist convention for Criteo-style data).

        Trees produced are the same arrays as :meth:`fit`, so
        :meth:`predict` and checkpointing work unchanged.  A model that
        already holds trees CONTINUES from them (the elastic-recovery
        resume contract): existing margins replay over the binned pages
        before the ``n_trees`` additional rounds run, and round-indexed
        sampling draws use the global round number — a recovery replay
        reproduces the uninterrupted run's draws.

        Device memory contract: bounded by
        ``DMLC_TPU_EXTERNAL_DEVICE_BUDGET`` (bytes, default 6 GiB).
        When the whole binned set + per-row state fit the budget (and no
        sampling is active — see below) the in-core chunked engine runs
        (identical splits, ~25 rounds per dispatch); otherwise the
        chunk-streaming engine re-uploads bins per level while per-row
        state (y/w/preds/g/h/node, 12+12·num_class B/row) stays
        resident — that row-state floor is the engine's minimum
        residency, so datasets beyond ``budget/(12+12K)`` rows must
        shard across workers (PARITY.md §2b records this trade against
        the r3 per-page mode, whose unbounded-rows promise cost
        O(pages·depth) host-synced dispatches per round).

        ``cache_device=True`` forces full residency regardless of the
        budget.  Single-worker cache_device runs the in-core chunked
        engine: identical splits; leaf values carry the histogram-cumsum
        precision note, and with ``subsample``/``colsample_bytree`` < 1
        the *random draws* come from the device PRNG instead of the
        streaming engine's numpy PRNG, so the same seed selects a
        different (equally distributed) sample across the two modes.
        The DEFAULT path never has that ambiguity: with sampling active
        it always uses the streaming engine's numpy draws, whatever the
        dataset size.
        """
        from dmlc_core_tpu.base import compile_cache as _cc
        from dmlc_core_tpu.ops.quantile import SketchAccumulator
        from dmlc_core_tpu.parallel import collectives as coll

        # the _ext_* jits (and the cached route's round program) all
        # land in the persistent compile cache, so a relaunch — the
        # elastic-recovery restart case — skips their compiles
        _cc.configure()
        p = self.param
        CHECK(not (p.monotone_constraints
                   and any(int(v) for v in p.monotone_constraints)),
              "fit_external: monotone_constraints not supported — use fit()")
        CHECK("c" not in p.feature_types,
              "fit_external: categorical features (feature_types) are the "
              "in-core engine's — the page sketch has no category→bin "
              "table; use fit() / make_device_data()")
        CHECK(not p.objective.startswith("rank:"),
              f"fit_external: {p.objective} needs the grouped in-core "
              "layout — use fit(X, y, qid=...)")
        CHECK(not self._missing,
              "fit_external: this model was trained in missing mode "
              "(NaN bin + learned directions); the streaming engine "
              "builds standard cuts and would silently misread the top "
              "value bin as missing mass — continue with fit(), or use "
              "a fresh model")
        if p.scale_pos_weight != 1.0:
            # fail BEFORE the full-dataset sketch pass, not per page
            CHECK(p.objective == "binary:logistic",
                  f"scale_pos_weight only applies to binary:logistic "
                  f"(objective is {p.objective!r})")
        B = p.n_bins

        # -- pass 1: streaming sketch --------------------------------------
        F = max(num_col or 0, row_iter.num_col)
        if coll.world_size() > 1:
            # sparse shards can disagree on the max feature index; the
            # sketch allgather and histogram allreduce need one global F
            # (reference world: rabit allreduce-max of num_col)
            F = int(coll.allreduce(np.asarray([F], np.int64), op="max")[0])
        CHECK(F > 0, "fit_external: empty input")
        if cuts is not None:
            self.cuts = cuts
        else:
            sketch: Optional[SketchAccumulator] = None
            for block in row_iter:
                X = block.to_dense(F)
                if sketch is None:
                    sketch = SketchAccumulator(F, n_summary=max(8 * B, 64),
                                               buffer_pages=sketch_pages)
                # scaled weights here too: the cuts an explicit weight
                # vector would produce and the spw cuts must match
                sketch.add(X, self._fold_scale_pos_weight(
                    block.label, block.weight))
            CHECK(sketch is not None, "fit_external: empty input")
            self.cuts = sketch.finalize(B, allgather_fn=self._maybe_allgather())

        # -- pass 2: bin pages (uint8, FEATURE-major like fit()) -----------
        pages: List[Dict[str, Any]] = []   # "bins" is a jax.Array when cache_device
        # DMLC_TPU_BIN_BACKEND=cpu (see _host_bin_requested) bins pages on
        # the host backend and uploads nothing per page: the cached
        # path then uploads the 4x-smaller uint8 matrix ONCE at concat
        # time instead of 365 per-page f32 slabs.  Unset = device
        # binning.
        host_bin = _host_bin_requested()
        cuts_for_bin = np.asarray(self.cuts) if host_bin else None
        for block in row_iter:
            X = block.to_dense(F)
            # in pass 2 so it runs on the explicit-cuts path too (pass 1
            # is skipped there): plain binning would silently alias
            # NaN into the top value bin
            CHECK(not np.isnan(X).any(),
                  "fit_external: NaN features are only supported by "
                  "the in-core fit (learned missing direction) — "
                  "impute before streaming, or fit in-core")
            if host_bin:
                bins = _host_bin_t(X, cuts_for_bin)
            else:
                bins = apply_bins_t(jnp.asarray(X), self.cuts)  # [F, rows]
                if not cache_device:
                    bins = np.asarray(bins)  # spill to host; one page on
                                             # device at a time (out-of-core)
            w = (np.asarray(block.weight, np.float32)
                 if block.weight is not None else np.ones(len(X), np.float32))
            w = self._fold_scale_pos_weight(
                np.asarray(block.label, np.float32), w)
            pages.append({
                "bins": bins,
                "y": np.asarray(block.label, np.float32),
                "w": w,
            })
        # empty shard pages are legal; an unset num_class is learned from
        # the first page's labels (give it where pages hold few classes)
        for pg in pages:
            self._settle_num_class(pg["y"])

        distributed = coll.world_size() > 1
        if cache_device and not distributed:
            return self._fit_external_cached(pages, F, eval_every,
                                             warmup_rounds)
        # auto-residency (VERDICT r3 #3): when the binned data + per-row
        # state + the cached engine's concat transient fit the device
        # budget, the streaming loop would be pure dispatch overhead —
        # route to the in-core engine (identical splits, ~25 rounds per
        # dispatch).  The budget knob keeps the bounded-memory promise
        # explicit instead of implicit-per-page.  With sampling active
        # the chunked engine runs even under budget: the cached engine
        # draws from the device PRNG, and auto-routing would make the
        # same seed's sampled rows depend on dataset size vs budget —
        # the chunked engine reproduces the page-stream numpy draws at
        # any size.
        N_total = sum(len(pg["y"]) for pg in pages)
        from dmlc_core_tpu.base.parameter import get_env
        budget = get_env("DMLC_TPU_EXTERNAL_DEVICE_BUDGET", 6 << 30, int)
        row_state = 12 + 12 * p.num_class    # y/w/node + preds/g/h per class
        no_sampling = p.subsample >= 1.0 and p.colsample_bytree >= 1.0
        if (not distributed and no_sampling
                and N_total * (2 * F + row_state) <= budget):
            LOG("INFO", "fit_external: %d rows x %d feats fit the device "
                "budget (%d MiB; DMLC_TPU_EXTERNAL_DEVICE_BUDGET) - using "
                "the device-cached engine", N_total, F, budget >> 20)
            return self._fit_external_cached(pages, F, eval_every,
                                             warmup_rounds)
        return self._fit_external_chunked(pages, F, eval_every, distributed,
                                          budget=budget,
                                          cache_all=cache_device,
                                          warmup_rounds=warmup_rounds)

    def _fit_external_cached(self, pages, F: int, eval_every: int,
                             warmup_rounds: int = 0) -> "HistGBT":
        """Device-cached external-memory training = the in-core engine.

        With the binned pages resident in HBM there is nothing
        out-of-core left per round, so the pages concatenate into one
        feature-major bin matrix and boosting runs through the same
        chunked-scan machinery as :meth:`fit` — ONE dispatch per ~25
        rounds instead of O(pages·depth) host-driven dispatches per
        round (each paying per-dispatch latency).

        Memory note: the page concatenation transiently needs ~2× the
        binned matrix in HBM (sources + destination) before the page
        refs drop; steady-state residency equals the page loop's.  If
        that transient doesn't fit, use ``cache_device=False``.
        """
        p = self.param
        y = np.concatenate([pg["y"] for pg in pages])
        w = np.concatenate([pg["w"] for pg in pages])
        n = len(y)
        n_pad = (-n) % self._pad_multiple()
        # overlap the round-program compile with the page concat +
        # upload below (same handle fit()/fit_device use; see
        # histgbt._RoundProgramWarmup — _boost_binned joins it)
        self._maybe_start_warmup(F, n + n_pad)
        host_pages = isinstance(pages[0]["bins"], np.ndarray)
        if host_pages and self._sharded_ingest_ok() \
                and self.mesh.shape["data"] > 1:
            # multi-chip sharded staging: stream the binned host pages
            # through the per-chip ingest — each chip receives only its
            # own row slice, where the global-put fallback below stages
            # the FULL matrix through jax's global-array path first.
            # Binned bytes are placed, not recomputed, so the result is
            # byte-identical either way.
            bins_t = self._ingest_slabs_sharded(
                (pg["bins"] for pg in pages), n, n + n_pad, F,
                binned=True)
            pages.clear()
            if n_pad:
                y = np.concatenate([y, np.zeros(n_pad, np.float32)])
                w = np.concatenate([w, np.zeros(n_pad, np.float32)])
        else:
            if host_pages:
                # host pages (auto-residency route): concatenate on host
                # so the device sees ONE upload, not one per page
                # (per-transfer latency ~365 times otherwise)
                bins_t = jnp.asarray(
                    np.concatenate([pg["bins"] for pg in pages], axis=1))
            else:
                bins_t = jnp.concatenate(
                    [jnp.asarray(pg["bins"]) for pg in pages], axis=1)
            pages.clear()                 # free the per-page device refs
            if n_pad:
                bins_t = jnp.pad(bins_t, ((0, 0), (0, n_pad)))
                y = np.concatenate([y, np.zeros(n_pad, np.float32)])
                w = np.concatenate([w, np.zeros(n_pad, np.float32)])
            bins_t = jax.device_put(
                bins_t, NamedSharding(self.mesh, P(None, "data")))
        row_sharding = NamedSharding(self.mesh, P("data"))
        y_d = jax.device_put(y, row_sharding)
        w_d = jax.device_put(w, row_sharding)
        margin_sharding = NamedSharding(self.mesh, self._margin_spec())
        preds = jax.device_put(
            np.full(self._margin_shape(n + n_pad), p.base_score, np.float32),
            margin_sharding)
        n_prior = len(self.trees)
        if n_prior:
            # continued fit (elastic-recovery resume): replay the
            # existing ensemble's margins over the staged bins
            from dmlc_core_tpu.models.histgbt import (
                _transpose_from_feature_major_fn)

            bins_rm = _transpose_from_feature_major_fn(self.mesh)(bins_t)
            preds = self._apply_trees(bins_rm,
                                      self._stacked_trees(
                                          self.trees, engine="external"),
                                      preds)
            if preds.sharding != margin_sharding:
                preds = jax.device_put(preds, margin_sharding)

        preds = self._boost_binned(bins_t, y_d, w_d, preds, F,
                                   eval_every=eval_every,
                                   warmup_rounds=warmup_rounds,
                                   round_offset=n_prior)
        # same post-fit contract as fit(): train_margins() works after a
        # cache_device external fit too (padding sliced off by the
        # recorded real-row count)
        self._train_preds = preds
        self._n_real_rows = n
        return self

    def _fit_external_chunked(self, pages, F: int, eval_every: int,
                              distributed: bool, budget: int,
                              cache_all: bool = False,
                              warmup_rounds: int = 0) -> "HistGBT":
        """Bounded-device-memory boosting over page-stacked chunks.

        Replaces the r3 per-page loop, which paid O(pages·depth)
        host-SYNCED device round-trips per boosting round.  The
        restructure (VERDICT r3 #3; reference seam: disk_row_iter.h's
        page-cached training loop, SURVEY.md §2b):

        * pages concatenate into a handful of fixed-shape chunks sized
          so ONE chunk's bins plus the always-resident per-row state
          (y/w/preds/g/h/node, 12+12K B/row) fit
          ``DMLC_TPU_EXTERNAL_DEVICE_BUDGET``; non-resident chunk bins
          re-upload per level (the out-of-core price), asynchronously;
        * every per-level product — node histograms, split choice, node
          routing, leaf sums, margin updates — stays on device; the only
          host sync is ONE packed fetch per finished tree;
        * per round: O(depth·chunks) asynchronous dispatches, zero
          intermediate host syncs (vs O(pages·depth) synced fetches).

        Sampling reproduces the r3 page loop's draws exactly: colsample
        masks use the same [seed, round, 1] host RNG; subsample keep
        masks draw per page in stream order from the same
        [seed, round, 2, rank] RNG before concatenating into chunks.

        Trees/predict/checkpoint contracts match :meth:`fit`.  Like the
        r3 page loop, ``_train_preds`` is not retained.
        """
        from dmlc_core_tpu.parallel import collectives as coll

        p = self.param
        obj = self._obj
        CHECK(p.grow_policy == "depthwise" and p.max_depth >= 1,
              "the external-memory engine grows depth-wise trees only: "
              "grow_policy='lossguide' (and max_depth=0) is the in-core "
              "engine's (fit / fit_device)")
        B, depth, K_cls = p.n_bins, p.max_depth, p.num_class
        n_leaf = 1 << depth
        half = max(n_leaf >> 1, 1)
        method = p.hist_method

        # -- chunk sizing against the device budget ---------------------
        page_rows = [len(pg["y"]) for pg in pages]
        N = sum(page_rows)
        CHECK(N > 0, "fit_external: no rows")
        row_state = 12 + 12 * K_cls
        if cache_all:
            # cache_device=True overrides the budget by contract (the
            # budget CHECK must not kill a forced-residency request)
            rows_per_chunk = N
        else:
            avail_bins = budget - N * row_state
            CHECK(avail_bins > F,
                  f"DMLC_TPU_EXTERNAL_DEVICE_BUDGET={budget} cannot hold "
                  f"the always-resident per-row state ({N} rows x "
                  f"{row_state} B = {N * row_state} B) plus one row of "
                  f"bins.  Raise the budget toward the chip's HBM, shard "
                  f"rows across more workers (each worker's floor is its "
                  f"own shard only), or force residency with "
                  f"cache_device=True.  This floor is the documented "
                  f"trade vs the r3 per-page mode — see fit_external "
                  f"docstring / PARITY.md §2b")
            rows_per_chunk = min(N, max(int(avail_bins // F), 1))
        n_chunks = -(-N // rows_per_chunk)
        Rc = -(-N // n_chunks)
        Rc = -(-Rc // 128) * 128            # lane-aligned fixed shape
        n_chunks = -(-N // Rc)              # rounding may empty the tail
        resident = n_chunks == 1

        # -- stack pages into chunk arrays, then free the pages ---------
        # device pages (distributed cache_device: pass 2 binned on
        # device) concatenate ON device — downloading them per page just
        # to re-upload would cost a blocked D2H fetch each
        device_pages = pages and not isinstance(pages[0]["bins"],
                                                np.ndarray)
        if device_pages:
            CHECK(n_chunks == 1,
                  "device-resident pages require cache_device residency")
            stacked = jnp.concatenate([pg["bins"] for pg in pages], axis=1)
            bins_d = [jnp.pad(stacked, ((0, 0), (0, Rc - N)))]
            bins_h = None
        else:
            bins_h = np.zeros((n_chunks, F, Rc), np.uint8)
        y_h = np.zeros((n_chunks, Rc), np.float32)
        w_h = np.zeros((n_chunks, Rc), np.float32)   # pad rows weigh 0
        pos = 0
        for pg in pages:
            r = len(pg["y"])
            done = 0
            while done < r:
                c, off = divmod(pos, Rc)
                take = min(r - done, Rc - off)
                if bins_h is not None:
                    bins_h[c, :, off:off + take] = \
                        pg["bins"][:, done:done + take]
                y_h[c, off:off + take] = pg["y"][done:done + take]
                w_h[c, off:off + take] = pg["w"][done:done + take]
                done += take
                pos += take
        n_valid = [max(0, min(Rc, N - c * Rc)) for c in range(n_chunks)]
        pages.clear()

        # -- device-resident per-row state ------------------------------
        y_d = [jnp.asarray(y_h[c]) for c in range(n_chunks)]
        w_d = [jnp.asarray(w_h[c]) for c in range(n_chunks)]
        mshape = (K_cls, Rc) if K_cls > 1 else (Rc,)
        init_margin = _ext_const_fn(mshape, p.base_score, "float32")
        preds_d = [init_margin() for _ in range(n_chunks)]
        zeros_node = _ext_const_fn((Rc,), 0, "int32")()
        if not device_pages:
            bins_d = ([jnp.asarray(bins_h[c]) for c in range(n_chunks)]
                      if resident else None)

        def chunk_bins(c):
            return bins_d[c] if bins_d is not None else jnp.asarray(bins_h[c])

        n_prior = len(self.trees)
        if n_prior:
            # continued fit (elastic-recovery resume): replay the
            # existing ensemble's margins chunk by chunk — the same
            # leaf values in the same order the incremental updates
            # applied them, so a resumed run carries bit-identical
            # margins into its first new round
            stacked_prior = self._stacked_trees(self.trees,
                                                engine="external")
            for c in range(n_chunks):
                preds_d[c] = self._apply_trees(
                    jnp.asarray(chunk_bins(c)).T, stacked_prior,
                    preds_d[c])

        # -- round pieces: module-level jits (_ext_*) bound to this fit's
        # config via static kwargs, so compiled programs persist across
        # fits/instances in jax.jit's own cache
        gh_fn = partial(_ext_gh, obj=obj)

        def adv_hist_lvl(bins, node, g, h, feat_prev, thr_prev, level, col):
            return _ext_adv_hist_lvl(bins, node, g, h, feat_prev, thr_prev,
                                     level=level, col=col, B=B,
                                     method=method)

        final_adv_leaf = partial(_ext_final_adv_leaf, n_leaf=n_leaf)
        sib_stack = partial(_ext_sib_stack, B=B)
        split_fn = _ext_split_fn(B, p.reg_lambda, p.gamma,
                                 p.min_child_weight, p.reg_alpha)
        upd_preds = partial(_ext_upd_preds, n_leaf=n_leaf)
        leaf_calc = partial(_ext_leaf_calc, lam=p.reg_lambda,
                            eta=p.learning_rate, alpha=p.reg_alpha)
        pack_tree = partial(_ext_pack_tree, half=half)
        eval_loss = partial(_ext_eval_loss, obj=obj)

        # Fine-grained hist-build / split-scan / leaf / apply timing:
        # this engine's phases are SEPARATE dispatches (unlike the fused
        # in-core round program), so block_until_ready_time can attribute
        # wall time per phase.  Opt-in: blocking after every phase
        # serializes host/device overlap, so production runs keep the
        # cheap per-round aggregate only.
        phases_on = (_metrics.enabled() and os.environ.get(
            "DMLC_METRICS_GBT_PHASES", "0") == "1")

        def timed_phase(phase, fn, *a, **kw):
            if not phases_on:
                return fn(*a, **kw)
            out, dt = block_until_ready_time(fn, *a, **kw)
            gbt_metrics()["phase"].observe(dt, engine="external",
                                           phase=phase)
            return out

        def grow_one_tree(col, feat_mask, g_d, h_d):
            """One level-wise tree; returns device (feats, thrs, gains,
            leaf) and the per-chunk leaf assignments — nothing fetched.
            Each level consumes every chunk's bins exactly once
            (advance-from-previous-split fused with the histogram build;
            the deepest advance fused with the leaf sums), so a streamed
            chunk pays depth+1 uploads per tree."""
            node = [zeros_node for _ in range(n_chunks)]
            feats, thrs, gains = [], [], []
            prev_hist = None
            feat = thr = None
            for level in range(depth):
                hist = None
                for c in range(n_chunks):
                    node[c], ph = timed_phase(
                        "hist", adv_hist_lvl, chunk_bins(c), node[c],
                        g_d[c], h_d[c], feat, thr, level, col)
                    hist = ph if hist is None else hist + ph
                if distributed:
                    hist = coll.allreduce_device(hist)
                    coll.record_hist_psum(hist.nbytes, engine="external")
                if level > 0:
                    hist = sib_stack(hist, prev_hist, level=level)
                prev_hist = hist
                feat, thr, gain = timed_phase("split", split_fn, hist,
                                              feat_mask)
                feats.append(feat)
                thrs.append(thr)
                gains.append(gain)
            gsum = hsum = None
            for c in range(n_chunks):
                g_c = g_d[c] if col is None else g_d[c][col]
                h_c = h_d[c] if col is None else h_d[c][col]
                node[c], gs, hs = timed_phase(
                    "leaf", final_adv_leaf, chunk_bins(c), node[c],
                    g_c, h_c, feat, thr)
                gsum = gs if gsum is None else gsum + gs
                hsum = hs if hsum is None else hsum + hs
            if distributed:
                gsum = coll.allreduce_device(gsum)
                hsum = coll.allreduce_device(hsum)
            return feats, thrs, gains, leaf_calc(gsum, hsum), node

        def unpack_tree(flat):
            fl = np.asarray(flat)           # the ONE per-tree host sync
            d = depth * half
            feats = fl[:d].astype(np.int32).reshape(depth, half)
            thrs = fl[d:2 * d].astype(np.int32).reshape(depth, half)
            gains = fl[2 * d:3 * d].reshape(depth, half)
            leaf = fl[3 * d:]
            return feats, thrs, gains, leaf

        def one_round(r, record):
            """One boosting round; ``record=False`` discards the result
            (warmup: compiles gh/hist/split/advance/leaf/pack programs
            and leaves preds/trees untouched)."""
            feat_mask = None                 # same RNG as the r3 page loop
            if p.colsample_bytree < 1.0:
                crng = np.random.default_rng([p.seed, r, 1])
                n_keep = max(1, int(np.ceil(p.colsample_bytree * F)))
                scores = crng.random(F)
                feat_mask = jnp.asarray(
                    scores <= np.sort(scores)[n_keep - 1])
            if p.subsample < 1.0:
                rrng = np.random.default_rng([p.seed, r, 2, coll.rank()])
                keep = np.zeros((n_chunks, Rc), np.float32)
                kpos = 0
                for pr in page_rows:         # per page, in stream order
                    draws = (rrng.random(pr) < p.subsample).astype(
                        np.float32)
                    done = 0
                    while done < pr:
                        c, off = divmod(kpos, Rc)
                        take = min(pr - done, Rc - off)
                        keep[c, off:off + take] = draws[done:done + take]
                        done += take
                        kpos += take
                wk = [jnp.asarray(w_h[c] * keep[c])
                      for c in range(n_chunks)]
            else:
                wk = w_d
            g_d, h_d = [], []
            for c in range(n_chunks):
                g, h = gh_fn(preds_d[c], y_d[c], wk[c])
                g_d.append(g)
                h_d.append(h)
            if K_cls == 1:
                feats, thrs, gains, leaf, node = grow_one_tree(
                    None, feat_mask, g_d, h_d)
                if not record:
                    unpack_tree(pack_tree(feats, thrs, gains, leaf))
                    return
                for c in range(n_chunks):
                    preds_d[c] = timed_phase("apply", upd_preds,
                                             preds_d[c], node[c], leaf,
                                             col=None)
                f, t, gn, lf = unpack_tree(pack_tree(feats, thrs, gains,
                                                     leaf))
                self.trees.append({"feat": f, "thr": t, "gain": gn,
                                   "leaf": lf})
            else:
                per_class = []
                for col in range(K_cls):
                    feats, thrs, gains, leaf, node = grow_one_tree(
                        col, feat_mask, g_d, h_d)
                    if not record:
                        unpack_tree(pack_tree(feats, thrs, gains, leaf))
                        continue
                    for c in range(n_chunks):
                        preds_d[c] = timed_phase("apply", upd_preds,
                                                 preds_d[c], node[c],
                                                 leaf, col=col)
                    per_class.append(unpack_tree(
                        pack_tree(feats, thrs, gains, leaf)))
                if not record:
                    return
                self.trees.append({
                    "feat": np.stack([t[0] for t in per_class]),
                    "thr": np.stack([t[1] for t in per_class]),
                    "gain": np.stack([t[2] for t in per_class]),
                    "leaf": np.stack([t[3] for t in per_class]),
                })

        t_w = get_time()
        if warmup_rounds > 0:
            # ONE discarded round compiles every per-level program (the
            # full set is ~2·depth+5 jits — compile time that must
            # not sit inside the timed region)
            one_round(0, record=False)
        warmup_s = get_time() - t_w
        if _metrics.enabled() and warmup_rounds > 0:
            gbt_metrics()["phase"].observe(warmup_s, engine="external",
                                           phase="warmup")

        t0 = get_time()
        for r in range(n_prior, n_prior + p.n_trees):
            # global round index: sampling RNG streams and eval logging
            # line up with an uninterrupted run when resuming
            t_r = get_time()
            one_round(r, record=True)
            if _metrics.enabled():
                # the per-tree unpack inside one_round already synced, so
                # this wall delta is a true round time, no extra fetch
                m = gbt_metrics()
                m["phase"].observe(get_time() - t_r, engine="external",
                                   phase="round")
                m["rounds"].inc(1, engine="external")
                m["trees"].inc(1, engine="external")
            if eval_every and (r + 1) % eval_every == 0:
                # mean of per-row losses across all chunks (pad rows
                # excluded by the static n_valid slice), then the
                # objective's finalizer — a chunk-wise mean of metrics
                # would be wrong for non-additive metrics
                num = sum(float(eval_loss(preds_d[c], y_d[c],
                                          nv=n_valid[c]))
                          for c in range(n_chunks) if n_valid[c])
                loss = obj.finalize_mean_loss(num / max(N, 1))
                LOG("INFO", "round %d: loss=%.5f", r + 1, loss)
        self.last_fit_seconds = get_time() - t0
        # the chunk loop has no dispatch-chunk evidence; stale numbers
        # from an earlier in-core fit must not describe this run
        self.last_chunk_times = []
        self.last_warmup_seconds = warmup_s if warmup_rounds > 0 else None
        # margins live padded per chunk, not as one train-order vector
        self._train_preds = None
        self._n_real_rows = None
        return self

