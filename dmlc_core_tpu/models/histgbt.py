"""Hist-method gradient-boosted trees, TPU-native.

The flagship consumer of the substrate (BASELINE config 1: XGBoost gbtree
hist on HIGGS, 8-way data-parallel).  Functional parity targets XGBoost's
``tree_method=hist`` core loop; the engine is a redesign for XLA:

* features are quantile-binned once (``ops.quantile``) to int bins —
  all tree growth then touches only the ``[n, F]`` bin matrix;
* trees grow **level-wise with static shapes**: every tree is a complete
  binary tree of ``max_depth`` levels; nodes whose best gain ≤ ``gamma``
  take a degenerate split that routes all rows left (children inherit the
  subtree's optimal weight, so semantics match an early-stopped leaf);
  no data-dependent control flow, so one XLA compilation serves every
  round;
* per-level node histograms come from ``ops.histogram`` and are **psum'd
  over the mesh's data axis inside the step** — the histogram-sync
  allreduce rides ICI as a single XLA collective (north star: replaces
  rabit's socket tree allreduce; SURVEY.md §5);
* the whole boosting round (grad/hess → depth×(hist → split → descend) →
  leaf values → prediction update) is ONE jitted ``shard_map`` program;
  rows (bins, labels, preds) stay sharded on device across rounds, only
  O(2^depth) tree arrays come back to host.

Sibling-subtraction (build only left children, derive right = parent −
left from the previous level's synced histogram) halves both the one-hot
matmul height and the per-level psum bytes; combined with the subtile-
packed Pallas kernel (ops/histogram.py) a depth-6 tree's histogram work
is ~1 full MXU row-pass instead of 6.
"""

from __future__ import annotations

import operator
import os
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from functools import lru_cache, partial
from itertools import pairwise
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P, Sharding,
                          SingleDeviceSharding)

from dmlc_core_tpu.base import compile_cache as _cc
from dmlc_core_tpu.base import metrics as _metrics
from dmlc_core_tpu.base.logging import CHECK, CHECK_EQ, LOG, log_fatal
from dmlc_core_tpu.base.parameter import Parameter, field, get_env
from dmlc_core_tpu.base.timer import get_time
from dmlc_core_tpu.utils.profiler import span
from dmlc_core_tpu.data.device_feed import assemble_row_sharded
from dmlc_core_tpu.data.iter import slab_shard_slices
from dmlc_core_tpu.ops import binlayout as _bl
from dmlc_core_tpu.ops.histogram import (build_histogram,
                                         descend_histogram,
                                         hist_class_blocks,
                                         hist_feature_blocks,
                                         hist_feature_dots,
                                         hist_node_blocks,
                                         hist_psum_bytes_per_round,
                                         hist_tile_rows,
                                         pallas_interpret,
                                         recluster_points,
                                         recluster_rows,
                                         resolve_hist_method,
                                         select_feature_bins,
                                         tile_aligned, tile_liveness)
from dmlc_core_tpu.ops.quantile import (CAT_MAX_CODE, apply_bins,
                                        apply_bins_missing, apply_bins_t,
                                        cat_bins_used, cat_scan, cat_tables,
                                        compute_cuts, mesh_nan_scan,
                                        nan_scan)
from dmlc_core_tpu.ops.table_select import (SET_FLAT_MAX, SET_WORD_BITS,
                                            SplitWord,
                                            chain_select, route_form,
                                            set_select, set_words,
                                            table_select)
from dmlc_core_tpu.parallel.mesh import device_count, local_mesh
from dmlc_core_tpu.models.gbt_objectives import (  # noqa: F401  (re-exports:
    # scripts/tests import these via models.histgbt — keep the names)
    EVAL_METRICS, OBJECTIVES, _METRICS_BY_OBJECTIVE, _Logistic,
    RankGroups, _ObjectiveBase, _PairwiseRank, _Softmax, _SquaredError,
    _metric_auc, fold_scale_pos_weight)
from dmlc_core_tpu.models.gbt_split import (  # noqa: F401  (re-exports)
    _advance_node, _host_bin_requested, _host_bin_t, _leaf_sums,
    _make_best_split, _maybe_l1, _soft_threshold, gbt_metrics)
from dmlc_core_tpu.models.histgbt_external import _ExternalMemoryEngine

__all__ = ["HistGBT", "HistGBTParam", "OBJECTIVES"]

#: process-wide compiled round programs, keyed on
#: :meth:`HistGBT._round_fn_cache_key`.  Entries live for the process
#: (compiled CPU/TPU executables are MB-scale; a test suite or sweep
#: creates a few dozen distinct configs at most).  Each entry's own
#: jax.jit cache additionally holds one executable per distinct padded
#: input shape — a long-lived many-shape process can
#: ``_ROUND_FN_CACHE.clear()`` to release everything.
_ROUND_FN_CACHE: Dict[tuple, Any] = {}

#: process-wide AOT-compiled round executables, keyed on
#: (:meth:`HistGBT._round_fn_cache_key`, n_features, n_padded).  The
#: executable level of ``_ROUND_FN_CACHE``: where that cache shares the
#: *jitted wrapper* (one compile per padded shape via jax.jit's own
#: cache), this one holds the ``lower().compile()`` results the
#: cold-start warmup produces, so a repeated fit at the same shape —
#: bench re-measure, elastic-recovery relaunch — dispatches with zero
#: trace/compile work.  Same lifetime/clearing story as
#: ``_ROUND_FN_CACHE``.
_AOT_EXEC_CACHE: Dict[tuple, Any] = {}


def _rounds_schedule(n_trees: int, eval_every: int = 0) -> Tuple[int, int]:
    """(rounds per dispatch K, remainder) — the dispatch chunking both
    ``_boost_binned`` and the cold-start warmup must agree on."""
    k_env = int(os.environ.get("DMLC_TPU_ROUNDS_PER_DISPATCH", 25))
    CHECK(k_env >= 1,
          f"DMLC_TPU_ROUNDS_PER_DISPATCH must be >= 1, got {k_env}")
    K = min(n_trees, k_env)
    if eval_every:
        # chunk boundaries must land on eval rounds: use the largest
        # divisor of eval_every ≤ K (gcd alone would collapse to 1
        # for e.g. eval_every=7, paying per-dispatch latency 7×)
        K = max(d for d in range(1, K + 1) if eval_every % d == 0)
    return K, n_trees % K


def _ingest_chunk_rows(ndev: int) -> int:
    """Rows per streamed-ingest chunk (``DMLC_INGEST_CHUNK_ROWS``,
    default 2M; 0 disables streaming), rounded down to a mesh-size
    multiple so every chunk device_puts onto the row sharding."""
    rows = get_env("DMLC_INGEST_CHUNK_ROWS", 2_000_000, int)
    if rows <= 0:
        return 0
    return max(1, rows // ndev) * ndev


def _hist_blocks(data_size: int) -> int:
    """Resolved deterministic-histogram block count ``C`` (0 = off).

    ``DMLC_HIST_BLOCKS=N`` (N>0) turns on the mesh-shape-INVARIANT
    histogram reduction: rows are cut into ``C`` fixed global blocks
    (``N`` rounded up to a power of two ≥ the data-axis size), each
    block's histogram is built separately, and all reductions — the
    per-shard fold AND the cross-chip combine — run the same fixed
    pairwise tree.  Because a shard's blocks form an aligned subtree of
    that tree, a 1-chip fit and an N-chip fit of the SAME global rows
    produce bit-identical sums, hence bit-identical trees (the
    single-chip-oracle contract of doc/performance.md).  The plain
    ``psum`` path (default) is faster but its accumulation order — and
    therefore last-ulp gains, and occasionally a near-tie split — varies
    with the mesh shape.
    """
    v = get_env("DMLC_HIST_BLOCKS", 0, int)
    if v <= 0:
        return 0
    CHECK(data_size & (data_size - 1) == 0,
          f"DMLC_HIST_BLOCKS needs a power-of-two data-axis size, "
          f"got {data_size}")
    c = 1
    while c < max(v, data_size):
        c <<= 1
    return c


def _bin_pack_requested() -> bool:
    """``DMLC_BIN_PACK=1``: pack two ≤16-bin features per byte (int4) in
    the transposed bin matrix (ops.binlayout), halving HBM bin traffic
    and psum bytes for narrow features.  Bit-identical histograms."""
    return os.environ.get("DMLC_BIN_PACK", "0") == "1"


def _feature_bundle_requested() -> bool:
    """``DMLC_FEATURE_BUNDLE=1``: fuse mutually-exclusive (near-one-hot)
    feature blocks into one multi-bin storage feature (EFB), with exact
    unbundling at split evaluation (ops.binlayout.detect_bundles)."""
    return os.environ.get("DMLC_FEATURE_BUNDLE", "0") == "1"


class _RoundPlan(NamedTuple):
    """Every choice the traced round program bakes in beyond the
    ``Parameter``'s fields and the mesh, as :meth:`HistGBT._round_plan`
    resolved it.  Hashable: it IS the non-param part of the
    round-program cache key, and the only thing besides the param that
    ``_build_round_fn`` reads."""
    n_features: int
    #: histogram engine of each BUILD in tree order
    hist_method: Tuple[str, ...]
    #: rows of each feature block of each Pallas build (``()`` for the
    #: other engines): what ``ops.build_histogram`` derives again from
    #: the same shapes when it traces — a record, it selects nothing
    hist_feature_blocks: Tuple[Tuple[int, ...], ...]
    #: nodes of each node block of each Pallas build, likewise a record:
    #: ``(n_build,)`` wherever one kernel call takes the build (every
    #: level up to depth 7 at 256 bins); the feature blocks above are
    #: those of a node block's calls
    hist_node_blocks: Tuple[Tuple[int, ...], ...]
    #: NaN is missing: the reserved bin, the two-direction split scan and
    #: the direction descend (``HistGBT._settle_missing_mode`` decided)
    missing: bool
    pallas_interpret: bool
    grow_policy: str
    #: leaves of a loss-guide tree: ``max_leaves`` and ``2^max_depth``,
    #: whichever is set and smaller (0 under depth-wise growth); a tree is
    #: a node list of ``2 * max_leaves - 1`` entries
    max_leaves: int
    layout: Optional[_bl.BinLayout]
    hist_blocks: int
    mesh_devices: int
    #: a ranking handle's static group table (its width buckets): the
    #: shapes of the gradient stage, ``None`` for every other objective
    rank: Optional[RankGroups] = None
    #: trees a round: the classes of a ``multi:*`` objective (1 otherwise)
    num_class: int = 1
    #: classes of each kernel call of each Pallas build, likewise a
    #: record: ``(1,)`` for one tree a round; a ``multi:*`` round's K
    #: trees grow together and a build stacks as many classes a call as
    #: fit the MXU's 128 rows — the stacked kernel's engagement counter
    hist_class_blocks: Tuple[Tuple[int, ...], ...] = ()
    #: how ``route`` reads each row's split at each level below the root
    #: (``ops.table_select.route_form`` of the level's parents and the
    #: rows a device; ``()`` for loss-guide growth, which has no levels)
    route_forms: Tuple[str, ...] = ()
    #: where a node's split sits in the ONE int32 the packed forms look
    #: up; ``None`` where every level reads the unpacked tables
    route_word: Optional[SplitWord] = None
    #: the expansions before which a loss-guide tree re-orders a device's
    #: rows by leaf, after which its builds skip the tiles that hold none
    #: of their node (``ops.recluster_points``); ``()``: one scan, every
    #: build over all rows
    recluster_at: Tuple[int, ...] = ()
    #: per feature the bins of a CATEGORICAL column's table that can hold
    #: a training row (0: numeric), ``()`` where no column is categorical
    #: — the split scan's static shapes, and, through the largest, the
    #: words of a node's set that ``route`` looks up
    cat_bins: Tuple[int, ...] = ()

    @property
    def cat_words(self) -> int:
        """Words of 32 bins that ``route`` reads of a node's set."""
        return -(-max(self.cat_bins, default=0) // SET_WORD_BITS)

    def describe(self) -> Dict[str, Any]:
        """The JSON-serialisable record left on ``HistGBT.round_plan``."""
        lay = self.layout
        return {
            "hist_method": list(self.hist_method),
            "missing": self.missing,
            "pallas_interpret": self.pallas_interpret,
            "grow_policy": self.grow_policy,
            "bin_layout": (None if lay is None else
                           f"{lay.n_features}F->{lay.phys_rows}rows"
                           f"/{len(lay.pairs)}pairs"),
            # what the Pallas kernels issue per row tile, for the
            # record: nothing reads it to choose a path
            "hist_features": list(hist_feature_dots(self.n_features, lay)),
            "hist_feature_blocks": [list(b) for b in
                                    self.hist_feature_blocks],
            "hist_node_blocks": [list(b) for b in self.hist_node_blocks],
            "hist_class_blocks": [list(b) for b in self.hist_class_blocks],
            # route's engagement counter: entries a tree (a class counted
            # once) looked up as a chain of selects, of ONE packed word
            "route_lookups": {
                "form": "chain" if "chain" in self.route_forms else "pieces",
                "packed": self.route_word is not None,
                "chained_entries": sum(
                    1 << lv for lv, f in enumerate(self.route_forms)
                    if f == "chain")},
            "hist_blocks": self.hist_blocks,
            "mesh_devices": self.mesh_devices,
            # a multi:* round grows one tree a class from margins held
            # class-major on the device (rows on the lanes)
            "num_class": self.num_class,
            "trees_per_round": self.num_class,
            "margin_layout": "[num_class, n]" if self.num_class > 1
                             else "[n]",
            **(self.rank.describe() if self.rank is not None else {}),
            # a loss-guide tree: one single-node build an expansion
            **({"max_leaves": self.max_leaves,
                "expansions": self.max_leaves - 1,
                "recluster_at": list(self.recluster_at)}
               if self.grow_policy == "lossguide" else {}),
            # categorical columns: how many, their widest table, and how a
            # node's set is held and looked up
            **({"cat_features": sum(c > 0 for c in self.cat_bins),
                "cat_bins_max": max(self.cat_bins),
                "cat_set_form": {"form": "bit_words",
                                 "words": self.cat_words,
                                 "flat_up_to_nodes":
                                     SET_FLAT_MAX // self.cat_words}}
               if self.cat_bins else {}),
        }


#: device phase of the transposes and concats that put binned slabs into
#: the round program's feature-major layout (doc/observability.md)
_LAYOUT_SCOPE = jax.named_scope("dmlc.ingest.layout")
_to_feature_major = _LAYOUT_SCOPE(lambda b: b.T)


@lru_cache(maxsize=32)
def _pack_matrix_fn(mesh: Mesh, layout: "_bl.BinLayout"):
    """Jitted bin-matrix packing for one (mesh, layout): [F, n] uint8 →
    [phys_rows, n] with nibble pairs and bundles encoded; rows stay
    sharded P(None, "data") so the pack is shard-local."""
    return jax.jit(lambda bt: _bl.pack_matrix(bt, layout),
                   out_shardings=NamedSharding(mesh, P(None, "data")))


#: classes one batch of a multiclass round grows together: a ``[8, n]``
#: 32-bit array fills its sublane tiles, and no kernel call stacks more
#: (8 x the shortest left operand, A = 16, are the MXU's 128 rows:
#: ``ops.hist_class_blocks``).  Every ``[K, n]`` int32 intermediate of a
#: level is 4·K·n bytes (0.26 GiB at Covertype's 7 x 9.3M).
_CLASS_BATCH = 8


def _class_batches(n_class: int) -> Tuple[int, int]:
    """``(batches, classes a batch)`` of a round of ``n_class`` trees,
    from K alone: ``ceil(K / _CLASS_BATCH)`` equal batches."""
    batches = -(-max(n_class, 1) // _CLASS_BATCH)
    return batches, -(-max(n_class, 1) // batches)


def _per_class(g):
    """How a piece of ``grow_tree`` written for ONE tree runs where the
    gradients ``g`` carry a class axis (``[K, n]``): ``jax.vmap`` of it
    over the leading axis of every argument, applied INSIDE the piece's
    device scope, so the scope's name stays what the trace's readers
    look for (a scope entered under ``vmap`` reads ``vmap(dmlc...)``).
    No class axis (``[n]``: every round but a ``multi:*`` one): the
    piece itself, the program it always traced."""
    return jax.vmap if g.ndim == 2 else (lambda fn: fn)


def _grow_classes(grow, bins_tl, g_all, h_all, feat_mask):
    """The K trees of a multiclass round, every class from ``g_all[c]``
    / ``h_all[c]`` (``[K, n]``): ``(trees, deltas)`` with K leading.
    Up to ``_CLASS_BATCH`` classes are ONE batched ``grow``.  More go in
    equal batches from K alone (:func:`_class_batches`), the last filled
    up with classes of zero gradients whose trees are dropped — scanned,
    so the program stays one batch's whatever K is and a level's
    ``[K, n]`` intermediates stay one batch's too."""
    n_class = g_all.shape[0]
    batches, size = _class_batches(n_class)
    if batches == 1:
        return grow(bins_tl, g_all, h_all, feat_mask)

    def cut(a):
        a = jnp.pad(a, ((0, batches * size - n_class), (0, 0)))
        return a.reshape(batches, size, a.shape[1])

    _, out = jax.lax.scan(
        lambda _, gh: (None, grow(bins_tl, gh[0], gh[1], feat_mask)),
        None, (cut(g_all), cut(h_all)))
    return jax.tree.map(
        lambda a: a.reshape(batches * size, *a.shape[2:])[:n_class], out)


def _tree_fold(parts):
    """Fixed-order pairwise fold of a power-of-two list of arrays — the
    one reduction tree every mesh shape shares (see :func:`_hist_blocks`).
    ``((p0+p1)+(p2+p3))+...``: any aligned contiguous power-of-two
    sub-range folds to the exact value the full fold uses as its
    subtree, which is what makes per-shard partials composable."""
    while len(parts) > 1:
        parts = [parts[i] + parts[i + 1] for i in range(0, len(parts), 2)]
    return parts[0]


#: a single host-to-device transfer of 2**32 bytes or more crawls: one
#: v5e chip took 22.0-22.1 s to land 4.58 GB (0.21 GB/s) where 2.69 GB
#: land in ~1.2 s (PERF.md section 6, PR 42; on the four-chip host 4.48
#: GB to device 0 held the caller 24.9 s, PR 39, and 5.12 GB took 23.5 s,
#: PR 29).  A matrix that large goes in row pieces of at most
#: ``_PUT_PIECE_BYTES``, written into place on the device — on EVERY
#: path of ``_stage_device_data`` that puts a whole matrix (one slab,
#: multi-slab and mesh: PR 43); ``_put_matrix`` is the one helper that
#: cuts a put in pieces.
_PUT_CLIFF_BYTES = 1 << 32
_PUT_PIECE_BYTES = 1 << 31


@lru_cache(maxsize=32)
def _write_rows_fn(sharding: Optional[Sharding]):
    """Jitted ``whole[lo:lo + len(piece)] = piece`` in place (``whole``
    is donated; ``lo`` rides as an operand, so one program serves every
    piece of a shape).  ``sharding=None``: where the operands lie."""
    return jax.jit(
        lambda whole, piece, lo: jax.lax.dynamic_update_slice_in_dim(
            whole, piece, lo, axis=0),
        donate_argnums=(0,), out_shardings=sharding)


@lru_cache(maxsize=32)
def _empty_matrix_fn(sharding: Optional[Sharding], shape: tuple, dtype):
    """Jitted allocation of an uninitialised device matrix (per shape: a
    fresh closure would compile on every call).  ``sharding=None``: on
    the default device, uncommitted."""
    return jax.jit(partial(jnp.empty, shape, dtype), out_shardings=sharding)


def _put_matrix(X: np.ndarray,
                sharding: Optional[NamedSharding]) -> jax.Array:
    """``X`` on the device under ``sharding``: one put below the cliff
    (``_PUT_CLIFF_BYTES``), else row pieces put one after another —
    each waited for, so that no transfer queues behind another — and
    written into one array in place while the next piece travels.
    Every put is a ``dmlc.ingest.put`` span.

    ``sharding=None`` is ``jnp.asarray``'s placement: the default
    device, whole, UNCOMMITTED (one chip and several slabs, and a mesh
    whose rows cannot be placed chip by chip).  A mesh that can takes
    its rows as one shard a chip, its own paced pieces
    (:meth:`HistGBT._put_row_shards`): never the whole matrix on one
    of them."""
    if X.nbytes < _PUT_CLIFF_BYTES:
        with span("dmlc.ingest.put", bytes=X.nbytes):
            return jax.device_put(X, sharding)
    n = X.shape[0]
    rows = -(-n // -(-X.nbytes // _PUT_PIECE_BYTES))
    whole = _empty_matrix_fn(sharding, X.shape, X.dtype)()
    write = _write_rows_fn(sharding)
    for lo in range(0, n, rows):
        piece = X[lo:lo + rows]
        with span("dmlc.ingest.put", bytes=piece.nbytes):
            landed = jax.device_put(piece, sharding)
            landed.block_until_ready()
        whole = write(whole, landed, lo)
    return whole


@lru_cache(maxsize=32)
def _bin_chunk_fn(mesh: Mesh, miss_bin: Optional[int],
                  cat: Optional[tuple] = None):
    """Jitted per-(mesh, mode) chunk binning: digitize a row-sharded
    f32 slab against the cuts, feature-major as the count produces it —
    the streamed ingest's per-chunk kernel (cuts ride as a traced arg so
    one program serves every fit on the mesh).  ``cat``: the categorical
    columns, binned by their tables (None: no such column)."""
    return jax.jit(
        lambda xc, cuts: apply_bins_t(xc, cuts, miss_bin=miss_bin, cat=cat),
        out_shardings=NamedSharding(mesh, P(None, "data")))


@lru_cache(maxsize=32)
def _take_columns_fn(columns: Tuple[int, ...], mesh: Optional[Mesh]):
    """Jitted ``x[:, columns]`` as column slices laid side by side (a
    gather would go an element at a time), in a mesh's row shards where
    ``x`` lies in them: the numeric columns of a table that has
    categorical ones, for the cut sort."""
    return jax.jit(
        lambda x: jnp.stack([x[:, f] for f in columns], axis=1),
        out_shardings=(None if mesh is None
                       else NamedSharding(mesh, P("data", None))))


@lru_cache(maxsize=64)
def _concat_pieces_fn(n_pieces: int):
    """Jitted per-device concat of binned ingest pieces along rows —
    committed inputs keep it on the owning chip (sharded-ingest
    assembly; peak per-chip HBM ~2× that chip's uint8 slice)."""
    del n_pieces  # part of the key: one program per piece count
    return jax.jit(_LAYOUT_SCOPE(lambda *ps: jnp.concatenate(ps, axis=1)))


@lru_cache(maxsize=64)
def _concat_feature_major_fn(mesh: Mesh, n_pieces: int):
    """Jitted concat of binned chunks along rows (feature-major axis 1)
    — peak HBM is ~2× the uint8 matrix, vs the whole-matrix path's
    f32-plus-uint8 (~5×)."""
    del n_pieces  # part of the key: one program per piece count
    return jax.jit(_LAYOUT_SCOPE(lambda *ps: jnp.concatenate(ps, axis=1)),
                   out_shardings=NamedSharding(mesh, P(None, "data")))


class _RoundProgramWarmup:
    """Round-program compiles running concurrently with ingest.

    Created as soon as the round program's compile-time constants are
    pinned (cuts mode decided, shapes known) and joined by
    ``_boost_binned`` right before the first dispatch, so XLA compiles
    the K-round and remainder programs — BOTH in flight at once on
    :class:`~dmlc_core_tpu.base.compile_cache.BackgroundCompiler`
    workers, where the pre-overlap path compiled them serially inside
    the warmup dispatch — while the quantile sketch, binning and H2D
    staging run on the main thread.  Executables land in
    ``_AOT_EXEC_CACHE``; with the persistent compile cache warm the
    "compile" collapses to a disk read and ``join`` is ~instant.

    Any mismatch between what was warmed and what ``_boost_binned``
    actually needs (param mutated between kickoff and fit, different
    eval chunking, different padded shape) is detected by key equality
    and the handle is simply ignored — the inline jit path remains the
    source of truth, so overlap can never change results.  A compile
    the compiler REFUSES is not a mismatch: ``join`` re-raises it.
    """

    def __init__(self, model: "HistGBT", n_features: int, n_padded: int,
                 eval_every: int = 0) -> None:
        p = model.param
        K, rem = _rounds_schedule(p.n_trees, eval_every)
        sampling = p.subsample < 1.0 or p.colsample_bytree < 1.0
        mesh = model.mesh
        mat = NamedSharding(mesh, P(None, "data"))
        row = NamedSharding(mesh, P("data"))
        margin = NamedSharding(mesh, model._margin_spec())
        # packed/bundled layouts change the PHYSICAL bin-matrix height;
        # the layout is part of the plan, hence of the cache key, so a
        # mismatch between what was warmed and what fit dispatches is
        # caught by key equality
        plan = model._round_plan(n_features, n_padded)
        lay = plan.layout
        mat_rows = lay.phys_rows if lay is not None else n_features
        args = [
            jax.ShapeDtypeStruct((mat_rows, n_padded), np.uint8,
                                 sharding=mat),
            jax.ShapeDtypeStruct((n_padded,), np.float32, sharding=row),
            jax.ShapeDtypeStruct((n_padded,), np.float32, sharding=row),
            jax.ShapeDtypeStruct(model._margin_shape(n_padded),
                                 np.float32, sharding=margin),
        ]
        if plan.rank is not None:
            # a ranking handle's group table rides as operands
            args.append(model._obj.table_structs(mesh, n_padded))
        if sampling:
            args.append(jax.random.key(0))   # concrete: tiny, typed aval
        self._keys: Dict[str, tuple] = {}
        jobs: Dict[str, Any] = {}
        for label, n_rounds in (("kfn", K), ("rem", rem)):
            if n_rounds == 0:
                continue
            key = (model._round_fn_cache_key(plan, n_rounds), n_padded)
            self._keys[label] = key
            if key in _AOT_EXEC_CACHE:
                continue                     # warmed by an earlier fit
            jobs[label] = partial(self._compile, model, plan,
                                  n_rounds, tuple(args))
        self._bg = (_cc.BackgroundCompiler(jobs, what="incore_round")
                    if jobs else None)
        self.compile_seconds = 0.0
        self.join_wait_seconds = 0.0
        self.cache_verdict: Optional[str] = None

    @staticmethod
    def _compile(model: "HistGBT", plan: _RoundPlan, n_rounds: int,
                 args: tuple):
        return model._build_round_fn(plan, n_rounds).lower(*args).compile()

    def join(self) -> Dict[str, Any]:
        """Block until compiles finish (re-raising a failed one);
        publish executables; return label → executable."""
        if self._bg is not None:
            results = self._bg.join()
            self.compile_seconds = self._bg.compile_seconds
            self.join_wait_seconds = self._bg.join_wait_seconds
            self.cache_verdict = self._bg.cache_verdict
            for label, comp in results.items():
                _AOT_EXEC_CACHE[self._keys[label]] = comp
            self._bg = None
        return {label: _AOT_EXEC_CACHE[key]
                for label, key in self._keys.items()
                if key in _AOT_EXEC_CACHE}

    def matches(self, model: "HistGBT", plan: _RoundPlan, n_padded: int,
                K: int, rem: int) -> bool:
        """True iff the warmed programs are exactly the ones the
        imminent fit will dispatch (the plan carries ``n_features``)."""
        return self._keys == {
            label: (model._round_fn_cache_key(plan, n_rounds), n_padded)
            for label, n_rounds in (("kfn", K), ("rem", rem)) if n_rounds}


@lru_cache(maxsize=32)
def _transpose_to_feature_major_fn(mesh: Mesh):
    """Shared jitted ``[n, F] → [F, n]`` resharding transpose (per mesh —
    a fresh per-fit lambda would recompile every call)."""
    return jax.jit(
        _to_feature_major,
        out_shardings=NamedSharding(mesh, P(None, "data")))


@lru_cache(maxsize=32)
def _transpose_from_feature_major_fn(mesh: Mesh):
    """Inverse of :func:`_transpose_to_feature_major_fn`: ``[F, n] →
    [n, F]`` with rows back on the data axis — the margin-replay staging
    a resumed fit (elastic recovery) runs over a device-data handle."""
    return jax.jit(
        lambda b: b.T,
        out_shardings=NamedSharding(mesh, P("data", None)))


# shape-keyed caches are BOUNDED: one entry per distinct dataset size,
# and evicting the jit wrapper drops the last reference to its compiled
# executables (pre-cache, per-instance closures freed with the instance)
@lru_cache(maxsize=256)
def _init_margin_fn(mesh: Mesh, shape: tuple, base_score: float, spec: P):
    """Shared jitted on-device base-score fill (see
    :meth:`HistGBT._init_margin_device`): ``[n]``, or class-major
    ``[K, n]``, sharded by ``spec`` (:meth:`HistGBT._margin_spec`)."""
    return jax.jit(
        lambda: jnp.full(shape, base_score, jnp.float32),
        out_shardings=NamedSharding(mesh, spec))



class _RankStaging(NamedTuple):
    """What :meth:`HistGBT._regroup_ranking` hands the staging: the rows
    in query order and the group table, still on the host."""
    X: np.ndarray
    y: np.ndarray
    weight: Optional[np.ndarray]
    n_padded: int
    #: the padded layout's row of every row in query order (``None``
    #: where there are no pad rows: one chip)
    place: Optional[np.ndarray]
    #: the padded layout's row of every row the CALLER gave (-1: cut by
    #: ``max_group_size``) — what :meth:`HistGBT.train_margins` unwinds
    pos: np.ndarray
    groups: RankGroups
    table: Dict[str, Tuple[np.ndarray, ...]]


def _take_rows(X: np.ndarray, order: np.ndarray) -> np.ndarray:
    """``X[order]`` by a few threads (numpy's ``take`` releases the
    interpreter lock): the ONE host copy a ranking ingest makes."""
    out = np.empty((len(order),) + X.shape[1:], X.dtype)
    step = max(-(-len(order) // 8), 1)

    def part(lo):
        np.take(X, order[lo:lo + step], axis=0, out=out[lo:lo + step])

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(part, range(0, len(order), step)))
    return out


class HistGBTParam(Parameter):
    """Hyperparameters (XGBoost-compatible names where they exist)."""

    n_trees = field(int, default=100, lower_bound=1, description="boosting rounds")
    max_depth = field(int, default=6, lower_bound=0, upper_bound=12,
                      description="tree depth; 0 = no depth cap, under "
                                  "grow_policy='lossguide' with a "
                                  "max_leaves budget only")
    grow_policy = field(str, default="depthwise",
                        enum=["depthwise", "lossguide"],
                        description="depthwise: complete trees, a level at "
                                    "a time; lossguide: leaf-wise, the open "
                                    "leaf of highest gain next (LightGBM's "
                                    "policy), one histogram build an "
                                    "expansion")
    max_leaves = field(int, default=0, lower_bound=0,
                       description="leaf budget of a lossguide tree (0 = "
                                   "the depth cap alone, 2^max_depth); "
                                   "ignored by depthwise growth")
    n_bins = field(int, default=256, lower_bound=2, upper_bound=256,
                   description="feature quantization bins (max_bin)")
    learning_rate = field(float, default=0.3, lower_bound=0.0, description="eta")
    reg_lambda = field(float, default=1.0, lower_bound=0.0, description="L2 on leaf weights")
    reg_alpha = field(float, default=0.0, lower_bound=0.0,
                      description="L1 on leaf weights (XGBoost alpha: "
                                  "soft-thresholded gradient sums)")
    gamma = field(float, default=0.0, lower_bound=0.0, description="min split gain")
    min_child_weight = field(float, default=1.0, lower_bound=0.0)
    objective = field(str, default="binary:logistic",
                      enum=["binary:logistic", "reg:squarederror",
                            "multi:softmax", "multi:softprob",
                            "rank:pairwise",
                            "rank:ndcg", "rank:map"])
    max_group_size = field(int, default=0, lower_bound=0,
                           description="rank:pairwise — cap docs per "
                                       "query (0 = largest group; larger "
                                       "groups are truncated)")
    num_class = field(int, default=1, lower_bound=1,
                      description="classes of a multi:* objective; left "
                                  "at 1 it is learned from the first "
                                  "labels seen (largest label + 1)")
    base_score = field(float, default=0.0, description="initial raw margin")
    scale_pos_weight = field(float, default=1.0, lower_bound=0.0,
                             description="binary:logistic — weight "
                                         "multiplier for positive rows "
                                         "(imbalanced data; typical "
                                         "value: #neg/#pos)")
    subsample = field(float, default=1.0, lower_bound=0.0, upper_bound=1.0,
                      description="per-round row subsampling rate")
    colsample_bytree = field(float, default=1.0, lower_bound=0.0,
                             upper_bound=1.0,
                             description="per-tree feature sampling rate")
    seed = field(int, default=0, description="PRNG seed for sampling")
    eval_metric = field(str, default="",
                        enum=[""] + sorted(EVAL_METRICS),
                        description="validation metric (default: the "
                                    "objective's own)")
    monotone_constraints = field(list, default=(),
                                 description="per-feature -1/0/+1 monotone "
                                             "constraints (empty = none)")
    hist_method = field(str, default="auto",
                        enum=["auto", "segment", "pallas"],
                        description="histogram engine (ops.histogram)")
    feature_types = field(list, default=(),
                          description="per-feature 'q' (numeric) or 'c' "
                                      "(categorical: whole-number codes "
                                      ">= 0, split by partition); empty = "
                                      "all numeric (XGBoost's DMatrix "
                                      "feature_types)")
    max_cat_to_onehot = field(int, default=4, lower_bound=1,
                              description="a categorical feature of at "
                                          "most this many bins offers each "
                                          "single bin against the rest")
    max_cat_threshold = field(int, default=64, lower_bound=1,
                              description="most bins on the smaller side "
                                          "of a categorical partition")


class HistGBT(_ExternalMemoryEngine):
    """Train/predict API.

    ``mesh`` may be any Mesh with a ``data`` axis (default: 1-axis mesh
    over all local devices).  Rows are sharded over ``data``; everything
    else is replicated.  On a multi-host pod the same code runs with the
    global mesh — ``fit`` only touches process-local shards via
    ``device_put`` on a global sharding.
    """

    def __init__(self, param: Optional[HistGBTParam] = None, mesh: Optional[Mesh] = None,
                 **kwargs: Any):
        self.param = param or HistGBTParam()
        if kwargs:
            self.param.init(kwargs)
        self.mesh = mesh if mesh is not None else local_mesh()
        CHECK("data" in self.mesh.axis_names, "mesh needs a 'data' axis")
        # the field system's bounds are inclusive; 0.0 would silently
        # train all-degenerate trees (XGBoost restricts to (0, 1])
        CHECK(self.param.subsample > 0.0, "subsample must be in (0, 1]")
        CHECK(self.param.colsample_bytree > 0.0,
              "colsample_bytree must be in (0, 1]")
        # under multi:* a num_class left unset (1) is learned from the
        # labels where the data entry points scan them
        # (:meth:`_settle_num_class`), as XGBClassifier does
        if not self.param.objective.startswith("multi:"):
            CHECK(self.param.num_class == 1,
                  f"num_class > 1 requires a multi:* objective, "
                  f"got {self.param.objective!r}")
        if self.param.eval_metric:
            allowed = _METRICS_BY_OBJECTIVE[self.param.objective]
            CHECK(self.param.eval_metric in allowed,
                  f"eval_metric {self.param.eval_metric!r} incompatible "
                  f"with objective {self.param.objective!r} "
                  f"(allowed: {sorted(allowed)})")
        self._obj = OBJECTIVES[self.param.objective]
        # before this model's first program compiles (the cut programs
        # run ahead of the round-program warmup that used to be the
        # first to wire the cache): see _set_cache_options on why the
        # scopes in the programs depend on it
        _cc.configure()
        #: [F, n_bins-1]: a numeric feature's cut points; a CATEGORICAL
        #: feature's (``feature_types``) category→bin table — the code
        #: of bin k at k, -1 past the named bins (ops.quantile.cat_tables)
        self.cuts: Optional[jax.Array] = None
        CHECK(all(t in ("q", "c") for t in self.param.feature_types),
              f"feature_types holds 'q' (numeric) and 'c' (categorical), "
              f"got {list(self.param.feature_types)}")
        #: (cuts, :meth:`_cat_bins` of them): read off the tables once
        self._cat_bins_of: Optional[tuple] = None
        #: NaN-as-missing mode (XGBoost learned default direction),
        #: auto-detected from the training data: bin n_bins-1 is
        #: reserved for NaN, trees carry a per-node "dir" array, and
        #: descend routes missing rows by it.  Sticky for the model's
        #: lifetime (cuts/trees are mode-specific) and persisted.
        self._missing: bool = False
        self.trees: List[Dict[str, np.ndarray]] = []   # per-tree arrays
        #: device forest kept by :meth:`_stacked_trees`, chunk by chunk
        self._forest_chunks: Tuple["_ForestChunk", ...] = ()
        self._round_fn = None
        self.last_fit_seconds: Optional[float] = None
        #: per-chunk timing evidence (bench.py auditability): _boost_binned
        #: records (rounds_fetched, seconds_since_t0) as each dispatch
        #: chunk's trees arrive on host, so one slow dispatch is
        #: distinguishable from a slow steady state.  Timestamps ride
        #: the tree-fetch loop that already exists, so recording adds no
        #: device traffic and no pipeline break.
        self.last_chunk_times: List[Tuple[int, float]] = []
        self.last_warmup_seconds: Optional[float] = None
        #: cold-start breakdown of the last fit (doc/performance.md):
        #: bin = host wall of make_device_data's staging calls up to
        #: their last enqueue (the call is asynchronous: NOT when the
        #: binned matrix is ready);
        #: compile = round-program compile critical path (overlapped
        #: with bin when the warmup handle ran; None on the inline
        #: path, where compile hides inside the warm dispatch);
        #: warm_dispatch = the discarded warmup rounds' wall;
        #: compile_cache = "hit" | "miss" | None (no cache traffic)
        self.last_bin_seconds: Optional[float] = None
        self.last_compile_seconds: Optional[float] = None
        self.last_warm_dispatch_seconds: Optional[float] = None
        #: {trace, dispatch, device} split of warm_dispatch: trace =
        #: inline lower+compile of the dispatch programs; dispatch =
        #: async-enqueue wall of the exec warmup (a TPU backend only);
        #: device = its completion fetch.  Attributes a warmup
        #: regression to re-tracing vs dispatch latency vs device time.
        self.last_warmup_breakdown: Optional[Dict[str, float]] = None
        self.last_compile_cache: Optional[str] = None
        #: how the last fit dispatched its round programs: "aot" (the
        #: ``lower().compile()`` executables of the warmup) or "jit"
        self.last_dispatch: Optional[str] = None
        #: what the round program runs, resolved by
        #: :meth:`_round_plan` before tracing — histogram engine per
        #: level, growth policy, bin layout, deterministic blocks, mesh
        #: width.  The JSON view of the
        #: :class:`_RoundPlan` that ``_build_round_fn`` builds from;
        #: ``chip_smoke.py`` and the benchmark read it.
        self.round_plan: Optional[Dict[str, Any]] = None
        self._pending_warmup: Optional[_RoundProgramWarmup] = None
        #: active packed/bundled bin layout (ops.binlayout.BinLayout) of
        #: the device-resident bin matrix, or None for the plain uint8
        #: [F, n] layout.  Set by make_device_data, taken into the plan
        #: by _round_plan (hence part of the round-program cache key).
        self._bin_layout: Optional[_bl.BinLayout] = None
        self.best_iteration: Optional[int] = None
        self.best_score: Optional[float] = None
        self._early_stopped = False
        #: per-chunk validation curve of the last eval_set fit (see fit)
        self.eval_history: List[Tuple[int, float]] = []
        self.eval_metric_name: Optional[str] = None

    # ------------------------------------------------------------------
    # training
    # ------------------------------------------------------------------
    def fit(
        self,
        X: np.ndarray,
        y: np.ndarray,
        weight: Optional[np.ndarray] = None,
        eval_every: int = 0,
        warmup_rounds: int = 0,
        cuts: Optional[jax.Array] = None,
        eval_set: Optional[Tuple[np.ndarray, np.ndarray]] = None,
        early_stopping_rounds: int = 0,
        qid: Optional[np.ndarray] = None,
    ) -> "HistGBT":
        """Boost ``n_trees`` rounds.  ``warmup_rounds`` extra rounds are run
        and discarded first (compile + cache warm) so benchmark timing via
        ``last_fit_seconds`` covers steady state only.  ``cuts`` injects
        precomputed bin boundaries (else weighted quantile cuts are
        computed, merged across workers).

        ``eval_set=(Xv, yv)`` tracks validation loss at chunk boundaries;
        with ``early_stopping_rounds`` boosting stops once the validation
        loss hasn't improved for that many rounds (checked at chunk
        granularity, like XGBoost's per-iteration check rounded up).
        ``best_iteration``/``best_score`` record the winner and
        :meth:`predict` then uses trees up to ``best_iteration+1`` by
        default.

        ``qid`` (required for the ``rank:*`` objectives) groups rows
        into queries: ``make_device_data(qid=)`` puts the rows in query
        order, ragged, with shard boundaries on query boundaries —
        pairwise gradients stay shard-local (see :class:`_PairwiseRank`)
        — and the boosting is ``fit_device``'s: one ranking layout."""
        p = self.param
        X = np.ascontiguousarray(X, dtype=np.float32)
        y = np.ascontiguousarray(y, dtype=np.float32)
        self._rank_pos = None
        ranking = p.objective.startswith("rank:")
        if ranking:                  # make_device_data asks for the qid
            CHECK(eval_set is None,
                  f"{p.objective} eval_set not supported (metrics need "
                  "qid groups; use models.ranking.ndcg on predictions)")
        else:
            CHECK(qid is None, f"qid= only valid for rank objectives "
                  f"(objective is {p.objective!r})")
        n, F = X.shape
        CHECK_EQ(len(y), n, "X/y row mismatch")
        if early_stopping_rounds:
            CHECK(eval_set is not None,
                  "early_stopping_rounds needs an eval_set")

        self._settle_num_class(y)
        if p.monotone_constraints:
            CHECK_EQ(len(p.monotone_constraints), F,
                     "monotone_constraints length must equal n_features")
            # strict membership: 0.5 or "x" must be rejected, not silently
            # truncated to "no constraint" by an int() cast
            CHECK(all(v in (-1, 0, 1) for v in p.monotone_constraints),
                  "monotone_constraints values must be -1, 0 or +1")

        # continued training (xgb_model semantics): keep the existing bin
        # boundaries — the loaded trees' thresholds are only meaningful
        # against them — and start margins from the existing ensemble
        n_prior = len(self.trees)      # best_iteration indexes the FULL list
        continuing = n_prior > 0
        row_sharding = NamedSharding(self.mesh, P("data"))
        mat_sharding = NamedSharding(self.mesh, P("data", None))
        K_cls = p.num_class
        rank = None
        if continuing and not ranking:
            CHECK(self.cuts is not None, "continue-fit without cuts")
            self._check_nan_allowed(X, "fit (continued)")
            weight = self._fold_scale_pos_weight(y, weight)
            X, y, mask, n_pad = self._pad_rows(X, y, weight)
            # the warm-start branch needs row-major bins for the margin
            # replay, binned on device — except missing mode over a
            # process-spanning mesh, which must host-bin (NaN f32
            # cannot cross the multi-process device_put assert)
            if self._missing and self._mesh_spans_processes():
                # NaN f32 can't cross the multi-process device_put
                # equality assert (NaN != NaN) — ship NaN-free uint8
                # bins instead (see make_device_data)
                bins = jax.device_put(
                    np.ascontiguousarray(
                        _host_bin_t(X, np.asarray(self.cuts),
                                    missing=True).T),
                    mat_sharding)
            else:
                bins = self._bin_matrix(jax.device_put(X, mat_sharding))
            bins_t = _transpose_to_feature_major_fn(self.mesh)(bins)
            # the continue branch builds a plain [F, n] matrix — a packed
            # layout left over from an earlier make_device_data must not
            # leak into this fit's round program
            self._bin_layout = None
            y_d = jax.device_put(y, row_sharding)
            w_d = jax.device_put(mask, row_sharding)
            margin_shape = self._margin_shape(n + n_pad)
            # the margin replay stays ON DEVICE: a host round trip here
            # (the pre-r5 code) cannot even fetch the value when the
            # mesh spans processes (non-addressable shards) — the
            # elastic-recovery resume path is exactly that case.  The
            # base margin is laid out with the target sharding so the
            # replayed margins inherit it by propagation.
            tgt_sharding = NamedSharding(self.mesh, self._margin_spec())
            preds = self._apply_trees(
                bins, self._stacked_trees(self.trees),
                jax.device_put(np.full(margin_shape, p.base_score,
                                       np.float32), tgt_sharding))
            if preds.sharding != tgt_sharding:
                preds = jax.device_put(preds, tgt_sharding)
            preds.block_until_ready()      # bins feed the replay; only
            bins.delete()                  # delete after it completes
            del bins
        else:
            # a FRESH fit() always re-derives cuts from this X (the
            # pre-refactor contract): leftovers from an aborted fit or
            # an earlier fit_device must not silently quantize new data.
            # Handle-sharing reuse is make_device_data's own contract.
            # A continued RANKING fit stages its rows like a fresh one
            # (the group table is the handle's) against the cuts the
            # model has, and replays the ensemble over the handle.
            if continuing:
                CHECK(self.cuts is not None, "continue-fit without cuts")
            elif cuts is None:
                self.cuts = None
            dd = self.make_device_data(X, y, weight=weight, cuts=cuts,
                                       qid=qid)
            bins_t, y_d, w_d = dd["bins_t"], dd["y_d"], dd["w_d"]
            rank, self._rank_pos = dd.get("rank"), dd.get("rank_pos")
            preds = (self._replay_margin_device(dd) if continuing
                     else self._init_margin_device(dd["n_padded"]))

        # validation state (binned once; margins updated incrementally)
        eval_bins = eval_margin = yv_d = None
        if eval_set is not None:
            Xv = np.ascontiguousarray(eval_set[0], dtype=np.float32)
            yv = np.ascontiguousarray(eval_set[1], dtype=np.float32)
            self._check_nan_allowed(Xv, "eval_set")
            eval_bins = self._bin_eval_chunked(Xv)
            eval_margin = jnp.full(self._margin_shape(len(yv)),
                                   p.base_score, jnp.float32)
            if continuing:
                eval_margin = self._apply_trees(
                    eval_bins, self._stacked_trees(self.trees), eval_margin)
            yv_d = jnp.asarray(yv)
        self.best_iteration = None
        self.best_score = None
        self._early_stopped = bool(early_stopping_rounds)
        if p.eval_metric:
            metric_fn, maximize = EVAL_METRICS[p.eval_metric]
            metric_name = p.eval_metric
        else:
            metric_fn, maximize = self._obj.metric, False
            metric_name = "loss"
        state = {"best_at": 0, "eval_margin": eval_margin}
        #: validation curve [(global_round, score)], one point per
        #: dispatch chunk — the data behind XGBoost's evals_result()
        self.eval_history: List[Tuple[int, float]] = []
        self.eval_metric_name = metric_name if eval_set is not None else None

        def after_chunk(done, preds_c, trees_k):
            if eval_bins is None:
                return False
            # trees_k is ONE dispatch chunk's stacked dict — wrap it as
            # a single-chunk forest for the chunked _apply_trees
            state["eval_margin"] = self._apply_trees(
                eval_bins, [trees_k], state["eval_margin"])
            vloss = float(metric_fn(state["eval_margin"], yv_d))
            self.eval_history.append((n_prior + done, vloss))
            improved = (self.best_score is None
                        or (vloss > self.best_score if maximize
                            else vloss < self.best_score))
            if improved:
                self.best_score = vloss
                self.best_iteration = n_prior + done - 1
                state["best_at"] = done
            elif (early_stopping_rounds
                  and done - state["best_at"] >= early_stopping_rounds):
                LOG("INFO", "early stop at round %d (best %s=%.5f @ %d)",
                    done, metric_name, self.best_score, state["best_at"])
                return True
            return False

        preds = self._boost_binned(bins_t, y_d, w_d, preds, F,
                                   eval_every=eval_every,
                                   warmup_rounds=warmup_rounds,
                                   after_chunk=after_chunk,
                                   round_offset=n_prior, rank=rank)
        self._train_preds = preds
        self._n_real_rows = n
        return self

    def _regroup_ranking(self, X, y, qid, weight, rs) -> "_RankStaging":
        """Put the rows in QUERY ORDER and build the group table, inside
        the ``dmlc.ingest.host_prep.regroup`` span ``rs``.

        One stable sort by ``qid``: documents keep their input order
        inside a query, and nothing is padded to the longest query.  The
        queries are dealt to the mesh's ``data`` shards whole and in
        order, as evenly as their boundaries allow; only where a shard
        ends short of the longest shard do pad rows follow (weight 0, in
        no query): at most one query's documents a shard, nothing on one
        chip.  ``max_group_size`` caps a query at its first G documents
        in input order (XGBoost's lambdarank_truncation_level spirit —
        document counts, don't reorder); 0, the default, cuts nothing.
        Sets ``self._obj`` to the objective configured with the table's
        static part (:func:`rank_buckets`: width buckets, the same
        shapes on every shard)."""
        p = self.param
        n = len(y)
        CHECK_EQ(len(qid), n, "qid/X row mismatch")
        CHECK(n > 0, f"{p.objective} needs rows")
        CHECK(float(y.min()) >= 0.0,
              f"{p.objective}: relevance labels must be >= 0")
        order = np.argsort(qid, kind="stable")
        qs = qid[order]
        starts = np.flatnonzero(np.r_[True, qs[1:] != qs[:-1]])
        lens = np.diff(np.r_[starts, n])
        if p.max_group_size and int(lens.max()) > p.max_group_size:
            G = p.max_group_size
            within = np.arange(n) - np.repeat(starts, lens)
            order = order[within < G]
            lens = np.minimum(lens, G)
            LOG("WARNING", "%s: truncated %d docs beyond "
                "max_group_size=%d", p.objective, n - len(order), G)
        n_kept = len(order)
        ends = np.cumsum(lens)
        starts = ends - lens
        # whole queries to every shard, in order: shard k ends with the
        # last query that ends at or before k/K of the documents
        dsize = int(self.mesh.shape["data"])
        q_cut = np.r_[0, np.searchsorted(
            ends, n_kept * np.arange(1, dsize) / dsize, side="right"),
            len(lens)]
        row_cut = np.r_[0, ends][q_cut]
        unit = int(np.lcm(self._pad_multiple(), dsize)) // dsize
        per_shard = max(int(np.diff(row_cut).max()), 1)
        per_shard = -(-per_shard // unit) * unit
        n_padded = per_shard * dsize
        shard_of_row = np.repeat(np.arange(dsize), np.diff(row_cut))
        place = None
        if n_padded != n_kept:
            place = (np.arange(n_kept) - row_cut[shard_of_row]
                     + shard_of_row * per_shard)
        pos = np.full(n, -1, np.int64)
        pos[order] = np.arange(n_kept) if place is None else place
        sorted_already = n_kept == n and bool(
            np.array_equal(order, np.arange(n)))
        if not sorted_already:
            X = _take_rows(X, order)
            y = y[order]
            if weight is not None:
                weight = np.asarray(weight, np.float32)[order]

        y_by_shard = [y[row_cut[k]:row_cut[k + 1]] for k in range(dsize)]
        self._obj, table, members = OBJECTIVES[p.objective].from_queries(
            [lens[q_cut[k]:q_cut[k + 1]] for k in range(dsize)],
            y_by_shard, per_shard)
        groups = self._obj.groups
        if _metrics.enabled():
            for b, member in zip(groups.buckets, members):
                gbt_metrics()["rank_queries"].inc(
                    sum(len(m) for m in member), engine="incore",
                    bucket=str(b.width))
        rs.set(queries=len(lens), docs=n_kept, max_group=int(lens.max()),
               buckets=len(groups.buckets),
               pad_share=(n_padded - n_kept) / n_padded)
        return _RankStaging(X, y, weight, n_padded, place, pos, groups,
                            table)

    def _boost_binned(self, bins_t, y_d, w_d, preds, n_features,
                      eval_every=0, warmup_rounds=0, after_chunk=None,
                      chunk_callback=None, round_offset=0, rank=None):
        """Run ``n_trees`` boosting rounds over device-resident binned
        data (bins feature-major [F, n], rows sharded on the mesh's data
        axis).  Shared by :meth:`fit` and the cached external-memory
        path.  Appends trees to ``self.trees``, sets
        ``last_fit_seconds``, returns the final margins.

        Rounds run in chunks of K per dispatch (lax.scan inside the jitted
        program): per-dispatch + per-fetch latency would otherwise add to
        every round's compute; trees stay on device until the end.
        ``after_chunk(done, preds, trees_k) -> stop?`` hooks validation/
        early-stopping between dispatches.  ``rank`` is a ranking
        handle's group table (device arrays), an operand of every
        dispatch.
        """
        with span("dmlc.fit", rounds=self.param.n_trees,
                  mesh_devices=device_count(self.mesh)):
            return self._boost_rounds(
                bins_t, y_d, w_d, preds, n_features, eval_every,
                warmup_rounds, after_chunk, chunk_callback, round_offset,
                rank)

    def _boost_rounds(self, bins_t, y_d, w_d, preds, n_features,
                      eval_every, warmup_rounds, after_chunk,
                      chunk_callback, round_offset, rank):
        """:meth:`_boost_binned`'s work, inside its ``dmlc.fit`` span."""
        p = self.param
        # rounds per dispatch (_rounds_schedule): 25 amortizes
        # per-dispatch latency while keeping ≥2 evidence chunks at the
        # 100-round bench shape (the anomaly detector needs per-chunk
        # arrival deltas); overridable for experiments
        K, rem = _rounds_schedule(p.n_trees, eval_every)
        sampling = p.subsample < 1.0 or p.colsample_bytree < 1.0
        base_key = jax.random.key(p.seed) if sampling else None

        CHECK((rank is not None) == p.objective.startswith("rank:"),
              f"{p.objective}: a ranking fit needs a handle made with "
              "make_device_data(qid=...), and no other fit takes one")
        table = () if rank is None else (rank,)

        def run(fn, preds_c, done):
            if sampling:
                # chunk key derives from the GLOBAL round index (prior
                # rounds included) so a given round draws the same
                # sample no matter how rounds are chunked into
                # dispatches — or split across resumed fits (elastic
                # recovery replays a round with its original draw)
                return fn(bins_t, y_d, w_d, preds_c, *table,
                          jax.random.fold_in(base_key, round_offset + done))
            return fn(bins_t, y_d, w_d, preds_c, *table)

        # join the overlapped compile (make_device_data / fit_device
        # kicked it off before ingest); the AOT executables are used
        # only when they are exactly the programs this fit dispatches
        # AND the live buffers carry the shardings they were lowered
        # for — on any drift the jitted program dispatches instead
        # (usually a persistent-cache hit); ``last_dispatch`` says which
        warm = self._pending_warmup
        self._pending_warmup = None
        kfn = rem_fn = None
        join_wait = 0.0
        self.last_compile_seconds = None
        self.last_compile_cache = None
        row_sh = NamedSharding(self.mesh, P("data"))
        margin_sh = NamedSharding(self.mesh, self._margin_spec())
        shardings_ok = (
            bins_t.sharding == NamedSharding(self.mesh,
                                             P(None, "data"))
            and y_d.sharding == row_sh and w_d.sharding == row_sh
            and preds.sharding == margin_sh)
        if warm is not None:
            with span("dmlc.fit.join_warmup"):
                execs = warm.join()          # never leave workers behind
        # the ONE resolution of this fit's program choices: the key, the
        # build and the byte accounting below all read ``plan``
        plan = self._round_plan(n_features, int(bins_t.shape[1]))
        if warm is not None:
            if shardings_ok and warm.matches(
                    self, plan, int(bins_t.shape[1]), K, rem):
                kfn = execs.get("kfn")
                rem_fn = execs.get("rem")
                join_wait = warm.join_wait_seconds
                self.last_compile_seconds = warm.compile_seconds
                self.last_compile_cache = warm.cache_verdict
        using_aot = kfn is not None and (rem == 0 or rem_fn is not None)
        # the shared jitted program is resolved EITHER way (a dict hit
        # when the warmup worker or an earlier fit built it): it keeps
        # the process-wide ``_round_fn`` sharing contract
        kfn_jit = self._build_round_fn(plan, K)
        rem_jit = self._build_round_fn(plan, rem) if rem else None
        if kfn is None:
            kfn = kfn_jit
        if rem and rem_fn is None:
            rem_fn = rem_jit

        trace_s = dispatch_s = device_s = 0.0

        def warm_dispatch(kf, rf):
            # exec-warm on a copy so the real buffer stays valid and
            # model state is untouched (preds is donated).  The enqueue
            # returning is `dispatch`; the fetch of one element is
            # `device`
            nonlocal dispatch_s, device_s
            t_d = get_time()
            out = run(kf, jnp.copy(preds), 0)
            out2 = run(rf, jnp.copy(preds), 0) if rf is not None else None
            dispatch_s += get_time() - t_d
            t_v = get_time()
            np.asarray(out[0][:1])
            if out2 is not None:
                np.asarray(out2[0][:1])
            device_s += get_time() - t_v

        t_w = get_time()
        with span("dmlc.fit.warm_dispatch"):
            if warmup_rounds > 0 and not using_aot:
                # first-dispatch tracing + compilation pulled out of the
                # round loop: lower the exact programs against the LIVE
                # buffers (lowering never executes or donates) and compile —
                # a warm persistent cache collapses that to a disk read.
                # The executables are adopted exactly like the overlapped
                # warmup path's, and published for later fits only when the
                # buffers carry the canonical shardings they key on.
                t_tr = get_time()
                aot_args = (bins_t, y_d, w_d, preds) + table + (
                    (jax.random.fold_in(base_key, round_offset),)
                    if sampling else ())
                kfn = kfn_jit.lower(*aot_args).compile()
                if rem:
                    rem_fn = rem_jit.lower(*aot_args).compile()
                if shardings_ok:
                    n_padded = int(bins_t.shape[1])
                    _AOT_EXEC_CACHE[(self._round_fn_cache_key(plan, K),
                                     n_padded)] = kfn
                    if rem:
                        _AOT_EXEC_CACHE[(self._round_fn_cache_key(
                            plan, rem), n_padded)] = rem_fn
                using_aot = True
                trace_s = get_time() - t_tr
            # executed on a TPU only: the first dispatch pays real
            # one-time staging there (H2D layout, program load) worth
            # pulling out of the timed region; on CPU the compiled
            # programs have no such cost and an exec-warmup would just
            # run the whole K-round chunk twice
            if warmup_rounds > 0 and jax.default_backend() == "tpu":
                warm_dispatch(kfn, rem_fn)
            np.asarray(preds[:1])
        self.last_dispatch = "aot" if using_aot else "jit"
        self.last_warm_dispatch_seconds = get_time() - t_w
        self.last_warmup_seconds = join_wait + \
            self.last_warm_dispatch_seconds
        self.last_warmup_breakdown = {
            "trace": round(trace_s, 6),
            "dispatch": round(dispatch_s, 6),
            "device": round(device_s, 6),
        }
        if _metrics.enabled() and warmup_rounds > 0:
            gbt_metrics()["phase"].observe(self.last_warmup_seconds,
                                           engine="incore", phase="warmup")

        # cross-chip traffic accounting: the per-level histogram sync is
        # the ONLY collective in the round program, and it runs inside
        # the jitted dispatch where host instrumentation can't see it —
        # record the analytic per-round byte bill instead (the model
        # bench.py's hist_psum_bytes_per_round shares)
        psum_round_bytes = (hist_psum_bytes_per_round(
            p.max_depth, n_features, p.n_bins,
            layout=plan.layout, grow_policy=plan.grow_policy,
            max_leaves=plan.max_leaves)
            * max(p.num_class, 1) if plan.mesh_devices > 1 else 0)

        t0 = get_time()
        chunks: List[Any] = []
        hist_tiles = None
        done = 0
        while done < p.n_trees:
            fn = kfn if p.n_trees - done >= K else rem_fn
            k_now = K if fn is kfn else rem
            with span("dmlc.fit.dispatch", rounds=k_now,
                      first_round=round_offset + done):
                preds, trees_k = run(fn, preds, done)
            # a clustered leaf-wise tree says how many tiles its builds
            # computed: a record beside the node list, not part of it
            hist_tiles = trees_k.pop("hist_tiles", None)
            chunks.append(trees_k)        # stacked [k, ...] device arrays
            done += k_now
            if eval_every and done % eval_every == 0:
                loss = float(self._obj.metric(preds, y_d) if rank is None
                             else self._rank_loss(preds, rank))
                LOG("INFO", "round %d: loss=%.5f", done, loss)
            if after_chunk is not None and after_chunk(done, preds, trees_k):
                break
        self.last_chunk_times = []
        fetched = 0
        for trees_k in chunks:            # ONE host fetch per chunk.
            # Chunk i's trees arrive only once dispatch i finishes, while
            # later chunks keep computing — so these in-order arrival
            # timestamps give per-chunk durations for free (see
            # ``last_chunk_times`` doc in __init__).
            k = int(trees_k["feat"].shape[0])
            with span("dmlc.fit.fetch_chunk", trees=k):
                t_np = jax.tree.map(np.asarray, trees_k)
            fetched += k
            prev_t = (self.last_chunk_times[-1][1]
                      if self.last_chunk_times else 0.0)
            self.last_chunk_times.append((fetched, get_time() - t0))
            if _metrics.enabled():
                # per-round time from the arrival delta the fetch loop
                # already measures — no extra device sync
                m = gbt_metrics()
                m["phase"].observe(
                    (self.last_chunk_times[-1][1] - prev_t) / k,
                    engine="incore", phase="round")
                m["rounds"].inc(k, engine="incore")
                m["trees"].inc(k, engine="incore")
                if psum_round_bytes:
                    from dmlc_core_tpu.parallel import collectives as coll
                    coll.record_hist_psum(k * psum_round_bytes,
                                          engine="incore")
            if chunk_callback is not None:
                chunk_callback(*self.last_chunk_times[-1])
            self.trees.extend(
                {key: t_np[key][i] for key in t_np} for i in range(k))
        if hist_tiles is not None:
            # rows a device's kernels computed per build of the LAST tree
            builds = int(np.sum(self.trees[-1]["left"] >= 0)) + 1
            self.round_plan["hist_rows_per_build"] = int(
                int(np.asarray(hist_tiles)[-1]) * hist_tile_rows()
                // (plan.mesh_devices * builds))
        with span("dmlc.fit.sync"):
            np.asarray(preds[:1])         # real sync before stopping timer
        self.last_fit_seconds = get_time() - t0
        return preds

    def _maybe_allgather(self):
        from dmlc_core_tpu.parallel import collectives as coll

        if coll.world_size() > 1:
            return coll.allgather
        return None

    def _mesh_spans_processes(self) -> bool:
        """True when this model's mesh holds devices of other processes
        — the case where device_put of host data is a cross-process
        collective with jax's global-array equality assert."""
        import jax as _jax

        pid = _jax.process_index()
        return any(d.process_index != pid
                   for d in np.asarray(self.mesh.devices).flat)

    def _miss_bin(self) -> int:
        """The reserved NaN bin (``n_bins-1``; = #cuts+1 by the missing
        cut-width invariant), or -1 when not in missing mode — the ONE
        definition every binning/descend site shares."""
        return (int(self.cuts.shape[1]) + 1) if self._missing else -1

    def _nan_bin(self) -> Optional[int]:
        """:func:`apply_bins_t`'s ``miss_bin``: the reserved NaN bin in
        missing mode, else None (NaN is refused before it gets there)."""
        return self._miss_bin() if self._missing else None

    def _settle_num_class(self, y: np.ndarray) -> None:
        """Under a ``multi:*`` objective, where a data entry point scans
        its labels: learn ``num_class`` if it was left unset (the largest
        label + 1, as ``XGBClassifier`` does; it then stays the model's)
        and hold every label to ``[0, num_class)``."""
        p = self.param
        if not p.objective.startswith("multi:") or not len(y):
            return
        lo, hi = float(np.min(y)), float(np.max(y))
        if p.num_class == 1:
            CHECK(lo >= 0 and hi >= 1,
                  f"{p.objective}: num_class is unset and the labels "
                  f"[{lo:g}, {hi:g}] do not name two classes 0..K-1")
            p.num_class = int(hi) + 1
        CHECK(lo >= 0 and hi < p.num_class,
              f"{p.objective} labels must be in [0, {p.num_class})")

    def _fold_scale_pos_weight(self, y, weight):
        """Fold ``scale_pos_weight`` into the instance-weight vector —
        called by every data entry point (make_device_data → fit fresh
        + fit_device, fit's continue branch, fit_external's sketch AND
        page passes) so no path can silently drop the knob, and the
        scaling flows into the quantile sketch's weighting exactly like
        an explicit weight vector would.  Shared with GBLinear via
        :func:`fold_scale_pos_weight`."""
        return fold_scale_pos_weight(self.param, y, weight)

    def _cat_flags(self, n_features: Optional[int] = None
                   ) -> Optional[Tuple[bool, ...]]:
        """Per feature whether it is categorical (``feature_types`` says
        ``"c"``), or None where no feature is: the ``cat=`` of every
        binning call, and the gate of everything categorical."""
        types = self.param.feature_types
        if "c" not in types:
            return None
        if n_features is not None:
            CHECK_EQ(len(types), n_features,
                     "feature_types length must equal n_features")
        return tuple(t == "c" for t in types)

    def _cat_bins(self) -> Tuple[int, ...]:
        """Per feature the bins a categorical column's rows can hold (0:
        numeric), off the model's tables — ``()`` where no feature is
        categorical.  Host: the first call of a fresh model waits for
        the programs that make the cuts."""
        cat = self._cat_flags()
        if cat is None:
            return ()
        kept = self._cat_bins_of
        if kept is None or kept[0] is not self.cuts:
            kept = self._cat_bins_of = (
                self.cuts, cat_bins_used(np.asarray(self.cuts), cat))
        return kept[1]

    def _bin_matrix(self, x) -> jax.Array:
        """Digitize against the model's cuts, honoring missing mode
        (NaN → reserved bin ``n_bins-1``) and the categorical columns'
        tables."""
        if self._missing:
            return apply_bins_missing(x, self.cuts, self._miss_bin())
        return apply_bins(x, self.cuts, cat=self._cat_flags())

    def _check_nan_allowed(self, X: np.ndarray, where: str) -> None:
        """A non-missing model given NaN must fail loudly — plain
        binning would silently alias NaN into the top value bin."""
        if not self._missing and np.isnan(X).any():
            log_fatal(f"{where}: X contains NaN but this model was "
                      f"trained without missing support (train with NaN "
                      f"present to enable the learned default "
                      f"direction, or impute)")

    def _pad_multiple(self) -> int:
        """Row-padding granularity: the mesh device count, coarsened to
        the deterministic-histogram block count when ``DMLC_HIST_BLOCKS``
        is on (every block must have the same row count on every mesh
        shape, so rows pad to an lcm(devices, blocks) multiple)."""
        ndev = device_count(self.mesh)
        blocks = _hist_blocks(int(self.mesh.shape["data"]))
        if blocks:
            return int(np.lcm(ndev, blocks))
        return ndev

    def _sharded_ingest_ok(self) -> bool:
        """True when ingest may stage per-chip shard slabs directly onto
        their owning devices.
        Requires a single-process mesh whose rows shard over ``data``
        alone (every other axis size 1): per-device placement of row
        blocks is only well-defined when block ``k`` lives on exactly
        device ``k``.  The fallback — one global ``device_put`` per
        chunk — is bit-identical, just staged through jax's global-array
        path instead."""
        ndev = device_count(self.mesh)
        if ndev != int(self.mesh.shape["data"]):
            return False
        return not self._mesh_spans_processes()

    def _pad_rows(self, X, y, weight, staged: Optional[_RankStaging] = None):
        """Pad rows to a mesh-size multiple (a block multiple in
        deterministic-histogram mode) and build the weight mask
        (pad rows weigh 0, so they are invisible to cuts/grads/hists).
        A ranking handle's pad rows close each shard, not the matrix:
        ``staged`` says where every row goes.  ``X`` is None where the
        matrix already lies padded on the device."""
        n = len(y)
        if staged is not None and staged.place is not None:
            rows = staged.n_padded
            Xp = np.zeros((rows, X.shape[1]), np.float32)
            yp = np.zeros(rows, np.float32)
            mask = np.zeros(rows, np.float32)
            Xp[staged.place] = X
            yp[staged.place] = y
            mask[staged.place] = 1.0 if weight is None else weight
            return Xp, yp, mask, rows - n
        n_pad = (-n) % self._pad_multiple()
        if n_pad:
            if X is not None:
                X = np.concatenate([X, np.zeros((n_pad, X.shape[1]),
                                                np.float32)])
            y = np.concatenate([y, np.zeros(n_pad, np.float32)])
        mask = np.ones(n + n_pad, np.float32)
        if weight is not None:
            mask[:n] = weight
        if n_pad:
            mask[n:] = 0.0
        return X, y, mask, n_pad

    # ------------------------------------------------------------------
    # cold-start: overlapped compile + streamed ingest
    # ------------------------------------------------------------------
    def _maybe_start_warmup(self, n_features: int, n_padded: int,
                            eval_every: int = 0
                            ) -> Optional[_RoundProgramWarmup]:
        """Kick off the round-program compiles in the background (see
        :class:`_RoundProgramWarmup`); the handle parks on
        ``self._pending_warmup`` for ``_boost_binned`` to join.

        Multi-worker jobs and meshes that span processes return None
        and stay on the serial path: ``_boost_rounds`` compiles inline
        (a worker whose compile thread races its peers'
        collective-ordered device_puts is not worth the cold-start win
        there).  A program the compiler
        refuses raises — here if tracing fails, at the join if the
        background compile does."""
        from dmlc_core_tpu.parallel import collectives as coll
        if coll.world_size() > 1 or self._mesh_spans_processes():
            return None
        if self._pending_warmup is not None:
            # a matching handle is already in flight (bench kicks one off
            # before datagen; make_device_data must not duplicate the
            # compile work) — keep it; replace only on a real mismatch
            K, rem = _rounds_schedule(self.param.n_trees, eval_every)
            if self._pending_warmup.matches(
                    self, self._round_plan(n_features, n_padded), n_padded,
                    K, rem):
                return self._pending_warmup
        warm = _RoundProgramWarmup(self, n_features, n_padded, eval_every)
        self._pending_warmup = warm
        return warm

    def start_warmup(self, n_rows: int, n_features: int) -> bool:
        """Kick the round-program compiles in the background BEFORE the
        training data exists (the bench cold-start overlap: compile
        proceeds while datagen/ingest run).  Rows are padded exactly as
        ``make_device_data`` will pad them, so the handle this parks is
        the one ``fit_device`` later joins — the dedup guard in
        ``_maybe_start_warmup`` makes the ingest-time kick a no-op.

        Returns False without compiling when a packed bin layout is
        requested (``DMLC_BIN_PACK``/``DMLC_FEATURE_BUNDLE``) and for a
        ``rank:*`` objective: the layout, and a ranking handle's width
        buckets, are compile-time constants derived from the data, so
        the compile cannot start before ingest."""
        if _bin_pack_requested() or _feature_bundle_requested():
            return False
        if self._cat_flags() is not None:
            # the categorical columns' tables say how many bins each uses
            return False
        if self.param.objective.startswith("rank:"):
            # the group table's width buckets shape the gradient stage:
            # known only once make_device_data(qid=) has seen the queries
            return False
        n_padded = n_rows + ((-n_rows) % self._pad_multiple())
        return self._maybe_start_warmup(n_features, n_padded) is not None

    def _one_slab(self, n_rows: int) -> bool:
        """Is a matrix of ``n_rows`` a single slab of the streamed ingest
        (:meth:`_bin_ingest_streamed`)?"""
        chunk = _ingest_chunk_rows(device_count(self.mesh))
        return chunk <= 0 or n_rows <= chunk

    def _bin_ingest_streamed(self, X: np.ndarray,
                             mat_sharding: NamedSharding,
                             resident: Optional[jax.Array] = None
                             ) -> jax.Array:
        """Chunked, double-buffered host→device ingest + binning.

        ``resident`` is ``X`` (padded) already on the device under
        ``mat_sharding`` (the cut sort's operand — one chip's one slab,
        a mesh's row shards: ``_stage_device_data``): it is binned as it
        lies, each chip its own rows, and nothing is put; ``X`` is not
        read.

        The whole-matrix path ships the full f32 ``X`` to device and
        keeps it resident while the bin kernel runs — ~5× the binned
        matrix's HBM at peak.  Here rows stream in ``DMLC_INGEST_CHUNK_
        ROWS`` slabs through a depth-2 pipe (the ``data/device_feed``
        idiom): while chunk *i*'s bin kernel runs, chunk
        *i+1*'s H2D copy is already in flight, and each f32 slab's last
        reference drops as soon as its bins exist.  Peak residency: two
        f32 slabs + ~2× the uint8 matrix (the concat transient).
        Binning is per-element, so chunked output is bit-identical to
        the whole-matrix path (pinned by tests/test_compile_cache.py).

        The depth is ENFORCED: a slab is binned only once it has landed
        (``dmlc.ingest.put_wait``), so at most two puts are in flight.
        Left to run ahead — every call here is asynchronous — the host
        hands the runtime all the slabs at once, their host-side
        relayouts (one task a put) contend with the whole-matrix put the
        cut sort is waiting for, and a 24M × 28 ingest takes 8–12 s
        instead of 4.5 (PERF.md §5–6, PR 28).
        """
        fn = _bin_chunk_fn(self.mesh, self._nan_bin(), self._cat_flags())
        if resident is not None:
            with span("dmlc.ingest.stream", slabs=1):
                with span("dmlc.ingest.bin_dispatch"):
                    return fn(resident, self.cuts)
        n = X.shape[0]
        ndev = device_count(self.mesh)
        chunk = _ingest_chunk_rows(ndev)
        if self._one_slab(n):
            chunk = n                      # one slab: the whole matrix
        pieces: List[jax.Array] = []
        inflight: deque = deque()

        def bin_oldest():
            slab = inflight.popleft()
            with span("dmlc.ingest.put_wait", bytes=slab.nbytes):
                slab.block_until_ready()
            with span("dmlc.ingest.bin_dispatch"):
                pieces.append(fn(slab, self.cuts))

        with span("dmlc.ingest.stream", slabs=-(-n // chunk)):
            for lo in range(0, n, chunk):
                slab = X[lo:lo + chunk]
                with span("dmlc.ingest.put", bytes=slab.nbytes):
                    inflight.append(jax.device_put(slab, mat_sharding))
                if len(inflight) >= 2:   # keep one H2D copy in flight
                    bin_oldest()
            while inflight:
                bin_oldest()
            if len(pieces) == 1:
                return pieces[0]
            with span("dmlc.ingest.concat"):
                return _concat_feature_major_fn(
                    self.mesh, len(pieces))(*pieces)

    def _bin_eval_chunked(self, Xv: np.ndarray) -> jax.Array:
        """Validation-set binning through the chunked ingest path: the
        eval matrix streams device-ward slab by slab (double-buffered
        like :meth:`_bin_ingest_streamed`) instead of one whole-matrix
        ``jnp.asarray`` device_put, so a large eval_set never holds its
        full f32 next to its bins."""
        n = len(Xv)
        chunk = _ingest_chunk_rows(1)
        if chunk <= 0 or n <= chunk:
            return self._bin_matrix(jnp.asarray(Xv))
        pieces: List[jax.Array] = []
        inflight: deque = deque()
        for lo in range(0, n, chunk):
            inflight.append(jnp.asarray(Xv[lo:lo + chunk]))
            if len(inflight) >= 2:
                pieces.append(self._bin_matrix(inflight.popleft()))
        while inflight:
            pieces.append(self._bin_matrix(inflight.popleft()))
        return (pieces[0] if len(pieces) == 1
                else jnp.concatenate(pieces, axis=0))

    # ------------------------------------------------------------------
    # sharded ingest: per-chip slab staging (multi-chip data plane)
    # ------------------------------------------------------------------
    def _slab_stream(self, X: np.ndarray):
        """Yield ``X`` in ``DMLC_INGEST_CHUNK_ROWS`` row slabs (one slab
        when streaming is disabled) — the in-memory adapter feeding
        :meth:`_ingest_slabs_sharded`."""
        chunk = _ingest_chunk_rows(1) or len(X)
        for lo in range(0, len(X), chunk):
            yield X[lo:lo + chunk]

    def _put_row_shards(self, X: np.ndarray, n_padded: int) -> jax.Array:
        """``X`` [n, F] float32 on the mesh as ``[n_padded, F]`` in ROW
        shards, every row put ONCE and only to the chip that owns it
        (device ``k``: global rows ``[k·S, (k+1)·S)``, the cut of
        :meth:`_ingest_slabs_sharded`); the pad rows, the tail, are
        zeros.  No host copy of ``X`` is made: a piece is a view of its
        rows, but for the one the pad rows close.

        A shard goes in pieces of at most ``DMLC_INGEST_CHUNK_ROWS``
        rows (and ``_PUT_PIECE_BYTES``), chip after chip, written into
        place on their chip, and the puts are PACED like the one-chip
        slab stream's (``dmlc.ingest.put_wait``): at most two in flight.
        Four 1.12 GB shards handed to the runtime at once — or as one
        put under the row sharding — took 6.5 s to land on four v5e
        chips where one of them alone lands in 0.12 s; one after another
        0.48 s; in sixteen pieces two at a time 0.25 s (PERF.md section
        6, PR 52)."""
        n, F = X.shape
        devs = list(np.asarray(self.mesh.devices).flat)
        CHECK_EQ(n_padded % len(devs), 0, "padded rows must divide the mesh")
        S = n_padded // len(devs)
        step = max(1, min(_ingest_chunk_rows(1) or S,
                          _PUT_PIECE_BYTES // (F * 4), S))
        on = [SingleDeviceSharding(d) for d in devs]
        # a shard of one piece is that piece; else pieces are written
        # into an array allocated on their chip
        shards = [None if step == S
                  else _empty_matrix_fn(sh, (S, F), np.dtype(np.float32))()
                  for sh in on]
        inflight: deque = deque()

        def land_oldest():
            k, lo, piece = inflight.popleft()
            with span("dmlc.ingest.put_wait", bytes=piece.nbytes, chip=k):
                piece.block_until_ready()
            shards[k] = (piece if step == S
                         else _write_rows_fn(on[k])(shards[k], piece, lo))

        for lo in range(0, S, step):
            rows = min(step, S - lo)
            for k in range(len(devs)):
                piece = X[min(k * S + lo, n):min(k * S + lo + rows, n)]
                if len(piece) < rows:
                    piece = np.concatenate(
                        [piece, np.zeros((rows - len(piece), F), np.float32)])
                with span("dmlc.ingest.put", bytes=piece.nbytes, chip=k):
                    inflight.append((k, lo, jax.device_put(piece, on[k])))
                if len(inflight) >= 2:       # keep one H2D put in flight
                    land_oldest()
        while inflight:
            land_oldest()
        return assemble_row_sharded(shards, self.mesh, dim=0, axis="data")

    def _ingest_slabs_sharded(self, slabs, n_real: int, n_padded: int,
                              n_features: int,
                              binned: bool = False) -> jax.Array:
        """Stream f32 row slabs into the feature-major ``[F, n_padded]``
        uint8 bin matrix, placed PER CHIP: device ``k`` owns global rows
        ``[k·S, (k+1)·S)`` (``S = n_padded / ndev``), every slab is cut
        on those boundaries (:func:`~dmlc_core_tpu.data.iter.
        slab_shard_slices` — the ``nrows % (chips·chunk)`` tail math),
        and each piece is put — and on the device-bin route, binned —
        only on its owning chip.  Rows past ``n_real`` zero-fill (pad
        rows weigh 0).  The assembled global array
        (:func:`~dmlc_core_tpu.data.device_feed.assemble_row_sharded`)
        is byte-identical to a whole-matrix put, but no single device —
        and, given a slab iterator, no single HOST allocation — ever
        holds more than its own slice plus one slab: datasets larger
        than one chip's HBM stream straight onto the mesh
        (doc/performance.md "Multi-chip data parallelism").

        ``binned=True`` means the slabs arrive as ``[F, rows]`` uint8
        already (the external engine's page route) and are placed
        without re-binning."""
        ndev = device_count(self.mesh)
        CHECK_EQ(n_padded % ndev, 0, "padded rows must divide the mesh")
        S = n_padded // ndev
        devs = list(np.asarray(self.mesh.devices).flat)
        host_bin = binned or _host_bin_requested() or (
            self._missing and self._mesh_spans_processes())
        cuts_np = (np.asarray(self.cuts)
                   if host_bin and not binned else None)
        # a committed f32 piece pins the jit (and its uint8 output) to
        # that piece's device: each chip bins exactly its own row slice
        bin_fn = (None if host_bin
                  else partial(apply_bins_t, miss_bin=self._nan_bin(),
                               cat=self._cat_flags()))
        # a copy a chip (the cuts of a mesh's first ingest are committed
        # to the whole mesh until _stage_device_data has fetched them)
        cuts_dev = (None if host_bin
                    else [jax.device_put(self.cuts, d) for d in devs])
        pieces: List[List[Any]] = [[] for _ in range(ndev)]
        counts = [0] * ndev
        inflight: deque = deque()

        def put(piece: np.ndarray, k: int):
            with span("dmlc.ingest.put", bytes=piece.nbytes, chip=k):
                return jax.device_put(piece, devs[k])

        def bin_oldest():
            kq, xq = inflight.popleft()
            with span("dmlc.ingest.bin_dispatch", chip=kq):
                pieces[kq].append(bin_fn(xq, cuts_dev[kq]))

        lo = 0
        with span("dmlc.ingest.stream") as sp:
            n_slabs = 0
            for X_slab in slabs:
                n_slabs += 1
                L = X_slab.shape[1] if binned else len(X_slab)
                CHECK(lo + L <= n_real,
                      f"slab stream produced more than the declared "
                      f"{n_real} rows")
                if host_bin:
                    b_slab = (np.asarray(X_slab) if binned
                              else _host_bin_t(
                                  np.ascontiguousarray(X_slab, np.float32),
                                  cuts_np, missing=self._missing))  # [F, L]
                    for k, s_lo, s_hi, _dst in slab_shard_slices(lo, L, S):
                        pieces[k].append(put(np.ascontiguousarray(
                            b_slab[:, s_lo:s_hi]), k))
                        counts[k] += s_hi - s_lo
                else:
                    for k, s_lo, s_hi, _dst in slab_shard_slices(lo, L, S):
                        inflight.append((k, put(np.ascontiguousarray(
                            X_slab[s_lo:s_hi], dtype=np.float32), k)))
                        counts[k] += s_hi - s_lo
                        if len(inflight) >= 2:   # keep one H2D put in flight
                            bin_oldest()
                lo += L
            CHECK_EQ(lo, n_real,
                     "slab stream ended before the declared rows")
            while inflight:
                bin_oldest()
            sp.set(slabs=n_slabs)
        # pad-tail fill: pad ROWS are zero features, so the f32 routes
        # bin them through the cuts (bin-of-0.0 per feature) exactly
        # like make_device_data's padded matrix — the handles stay
        # byte-identical; pre-binned page slabs pad with bin 0, matching
        # the external engine's jnp.pad.  Either way pad rows weigh 0.
        pad_col = None
        if any(c < S for c in counts):
            pad_col = (np.zeros((n_features, 1), np.uint8) if binned
                       else _host_bin_t(
                           np.zeros((1, n_features), np.float32),
                           np.asarray(self.cuts),
                           missing=self._missing))
        for k in range(ndev):
            if counts[k] < S:
                pieces[k].append(jax.device_put(
                    np.ascontiguousarray(np.repeat(
                        pad_col, S - counts[k], axis=1)), devs[k]))
        with span("dmlc.ingest.concat"):
            per_dev = [p[0] if len(p) == 1
                       else _concat_pieces_fn(len(p))(*p) for p in pieces]
            return assemble_row_sharded(per_dev, self.mesh, dim=1,
                                        axis="data")

    def make_device_data_iter(
        self,
        slab_source: Any,
        n_features: Optional[int] = None,
        cuts: Optional[jax.Array] = None,
        n_rows: Optional[int] = None,
    ) -> Dict[str, Any]:
        """Out-of-core sharded ingest: build a :meth:`fit_device` handle
        from a STREAM of dense ``(X, y, w)`` slabs without ever
        materializing the dataset on host or on any single chip — the
        100M+-row path where the binned matrix exceeds one chip's HBM
        but fits the mesh's.

        ``slab_source`` is a callable returning a fresh iterator of
        ``(X [rows, F] f32, y [rows], w [rows] | None)`` numpy slabs in
        global row order (e.g. ``lambda: iter_dense_slabs(
        RowBlockIter.create("big.libsvm#cache.bin"), F, chunk)`` — the
        DiskRowIter/input_split page pipeline), or a plain iterable when
        ``cuts``, ``n_rows`` and ``n_features`` are all given (a
        one-pass ingest).  Without ``cuts`` a first streaming pass runs
        the bounded-memory quantile sketch (merged across workers like
        :meth:`fit_external`); the second pass bins each slab and places
        every piece on its owning chip only
        (:meth:`_ingest_slabs_sharded`).

        The handle is bit-compatible with :meth:`make_device_data`: the
        same global rows produce the same binned matrix, so trees grown
        from either handle are identical (pinned by
        tests/test_multichip.py and scripts/check_multichip.py).
        NaN/missing mode is not supported on this path (same contract
        as :meth:`fit_external`): impute before streaming or use
        :meth:`fit`.  An entry a CSR page does not hold is 0.0 in the
        slab (``RowBlock.to_dense_into``) and bins as 0.0: this path
        learns no default direction for absent entries.

        Slab width: a slab is ``slab_rows x F x 4`` bytes of float32 on
        the host and on the device, and TWICE that is in flight on each
        side (the source's staging buffer and this method's copy of it;
        the slab being binned and the next one's put): 65,536 rows of
        4,227 columns are 1.11 GB a slab.  Size ``slab_rows`` from the
        columns, not from a row count that suited a narrow table;
        :func:`~dmlc_core_tpu.data.iter.iter_dense_slabs` refuses a
        slab of 2^32 bytes or more.

        Host phases (doc/observability.md): the whole call is one
        ``dmlc.ingest`` operation (counts ``rows``, ``features``,
        ``pages``, ``slabs``, ``nnz``, ``dense_bytes``); the two passes
        are ``dmlc.ingest.iter.sketch_pass`` and
        ``dmlc.ingest.iter.bin_pass``; per slab ``dmlc.ingest.iter.copy``
        and, in the sketch pass, ``dmlc.ingest.iter.nan_scan`` and
        ``dmlc.ingest.iter.sketch_add``; the source's own
        ``dmlc.ingest.iter.page_wait`` (``DiskRowIter``) and
        ``dmlc.ingest.iter.densify`` (``Dataset.dense_slabs``) fall
        under them.
        """
        with span("dmlc.ingest") as sp:
            out = self._stage_device_data_iter(slab_source, n_features,
                                               cuts, n_rows, sp)
        self.last_bin_seconds = sp.seconds
        if _metrics.enabled():
            gbt_metrics()["phase"].observe(self.last_bin_seconds,
                                           engine="incore", phase="bin")
        return out

    def _stage_device_data_iter(self, slab_source, n_features, cuts,
                                n_rows, sp) -> Dict[str, Any]:
        """:meth:`make_device_data_iter`'s work, inside its
        ``dmlc.ingest`` span ``sp``."""
        from dmlc_core_tpu.ops.quantile import SketchAccumulator

        p = self.param
        CHECK(self._cat_flags() is None,
              "make_device_data_iter: streamed ingest does not support "
              "categorical features (feature_types) — the page sketch "
              "makes cut points, not category→bin tables; use "
              "make_device_data")
        CHECK(not self._missing,
              "make_device_data_iter: streamed ingest does not support "
              "missing mode (NaN bin) — impute, or fit in-core")
        CHECK(not self._mesh_spans_processes(),
              "make_device_data_iter: per-chip placement needs a "
              "single-process mesh (each process stages only local "
              "devices) — use fit_external for multi-worker jobs")
        CHECK_EQ(device_count(self.mesh), int(self.mesh.shape["data"]),
                 "make_device_data_iter: rows must shard over 'data' "
                 "alone (every other mesh axis size 1)")
        two_pass = cuts is None and self.cuts is None
        if two_pass or n_rows is None or n_features is None:
            CHECK(callable(slab_source),
                  "make_device_data_iter: slab_source must be a "
                  "callable (re-iterable) unless cuts, n_rows and "
                  "n_features are all provided")

        # -- pass 1 (when needed): streaming sketch + row count --------
        if cuts is not None:
            self.cuts = cuts
        if self.cuts is None or n_rows is None or n_features is None:
            sketch: Optional[SketchAccumulator] = None
            count = 0
            F_seen = n_features or 0
            with span("dmlc.ingest.iter.sketch_pass") as ps:
                n_slabs = 0
                for X_s, y_s, w_s in slab_source():
                    n_slabs += 1
                    # real copies (np.array): slab sources may yield
                    # views of a reused buffer, and the sketch's device
                    # ops consume the slab asynchronously
                    with span("dmlc.ingest.iter.copy",
                              bytes=X_s.shape[0] * X_s.shape[1] * 4):
                        X_s = np.array(X_s, dtype=np.float32)
                    with span("dmlc.ingest.iter.nan_scan",
                              bytes=X_s.nbytes):
                        CHECK(not np.isnan(X_s).any(),
                              "make_device_data_iter: NaN features are "
                              "only supported by the in-core fit — "
                              "impute before streaming")
                    F_seen = max(F_seen, X_s.shape[1])
                    count += len(X_s)
                    if self.cuts is None:
                        if sketch is None:
                            sketch = SketchAccumulator(
                                X_s.shape[1],
                                n_summary=max(8 * p.n_bins, 64))
                        with span("dmlc.ingest.iter.sketch_add",
                                  bytes=X_s.nbytes):
                            sketch.add(X_s, self._fold_scale_pos_weight(
                                np.array(y_s, dtype=np.float32),
                                None if w_s is None
                                else np.array(w_s, dtype=np.float32)))
                ps.set(slabs=n_slabs)
            CHECK(count > 0, "make_device_data_iter: empty input")
            n_rows = count if n_rows is None else n_rows
            CHECK_EQ(n_rows, count, "declared n_rows != streamed rows")
            n_features = F_seen
            if self.cuts is None:
                with span("dmlc.ingest.iter.sketch_finalize"):
                    self.cuts = sketch.finalize(
                        p.n_bins, allgather_fn=self._maybe_allgather())
        F = int(n_features)
        CHECK_EQ(int(self.cuts.shape[0]), F,
                 "cuts width does not match the streamed feature count")
        CHECK_EQ(int(self.cuts.shape[1]), p.n_bins - 1,
                 "cuts must be standard mode (n_bins-1 boundaries) for "
                 "the streamed ingest")

        n = int(n_rows)
        n_pad = (-n) % self._pad_multiple()
        n_padded = n + n_pad
        # compile the round ladder while the ingest streams below (the
        # cold-start overlap — same handle fit()/fit_device join)
        self._maybe_start_warmup(F, n_padded)

        # -- pass 2: stream bins per chip, accumulate y/w on host ------
        ys: List[np.ndarray] = []
        ws: List[np.ndarray] = []

        n_slabs = 0

        def x_slabs():
            nonlocal n_slabs
            for X_s, y_s, w_s in (slab_source() if callable(slab_source)
                                  else slab_source):
                n_slabs += 1
                # REAL copy, not ascontiguousarray: slab sources may
                # yield views of a reused staging buffer
                # (iter_dense_slabs' contract), and device_put can
                # alias host memory on the CPU backend — an in-flight
                # async H2D piece must never see the next slab's bytes
                with span("dmlc.ingest.iter.copy",
                          bytes=X_s.shape[0] * X_s.shape[1] * 4):
                    X_s = np.array(X_s, dtype=np.float32)
                y_np = np.array(y_s, dtype=np.float32)
                self._settle_num_class(y_np)
                ys.append(y_np)
                ws.append(self._fold_scale_pos_weight(
                    y_np, np.ones(len(y_np), np.float32) if w_s is None
                    else np.array(w_s, dtype=np.float32)))
                yield X_s

        with span("dmlc.ingest.iter.bin_pass") as pb:
            bins_t = self._ingest_slabs_sharded(x_slabs(), n, n_padded, F)
            pb.set(slabs=n_slabs)
        # pages and nnz are the source's count (count_in_op), over the
        # passes made; dense_bytes what ONE pass densifies
        sp.set(rows=n, features=F, slabs=n_slabs, dense_bytes=n * F * 4,
               pages=sp.counts.get("pages", 0),
               nnz=sp.counts.get("nnz", 0))
        y = np.concatenate(ys) if len(ys) > 1 else ys[0]
        mask = np.concatenate(ws) if len(ws) > 1 else ws[0]
        CHECK_EQ(len(y), n, "slab stream row count changed between passes")
        if n_pad:
            y = np.concatenate([y, np.zeros(n_pad, np.float32)])
            mask = np.concatenate([mask, np.zeros(n_pad, np.float32)])
        row_sharding = NamedSharding(self.mesh, P("data"))
        out = {
            "bins_t": bins_t,
            "y_d": jax.device_put(y, row_sharding),
            "w_d": jax.device_put(mask, row_sharding),
            "n": n,
            "n_padded": n_padded,
            "n_features": F,
        }
        return out

    # ------------------------------------------------------------------
    # reusable device-resident training data (DMatrix analogy)
    # ------------------------------------------------------------------
    def make_device_data(
        self,
        X: np.ndarray,
        y: np.ndarray,
        weight: Optional[np.ndarray] = None,
        cuts: Optional[jax.Array] = None,
        qid: Optional[np.ndarray] = None,
    ) -> Dict[str, Any]:
        """Quantize + upload a training set ONCE, for repeated fits.

        The reference's data-container role (SURVEY.md §2a ``data.h``
        RowBlock feeding repeated Boost calls; XGBoost's ``DMatrix``):
        bin boundaries are computed (or taken from ``cuts`` / the
        model's existing ``self.cuts``), the binned uint8 matrix lands
        on device feature-major, and the returned handle can be passed
        to :meth:`fit_device` any number of times with ZERO further H2D
        traffic — a repeated fit (hyperparameter retry, benchmark
        re-measure) pays neither the quantile pass nor the upload.

        Sets ``self.cuts`` if unset, so trees fitted from this handle
        predict correctly on raw features later.

        ``qid`` (the ``rank:*`` objectives need it, no other takes it)
        groups the rows into queries, in any order: the handle holds the
        rows in query order (one stable sort; ragged, nothing padded to
        the longest query and nothing cut unless ``max_group_size`` says
        so) and carries its GROUP TABLE — query boundaries in width
        buckets, device arrays made once (:class:`_PairwiseRank`) — so
        :meth:`fit_device` boosts on it like on any other handle.

        Asynchronous: the handle comes back once the last staging call
        is ENQUEUED (a mesh's first: once its cuts exist, fetched LAST);
        ready when drained (``jax.block_until_ready`` on its arrays).
        """
        with span("dmlc.ingest", rows=len(y)) as sp:
            out = self._stage_device_data(X, y, weight, cuts, sp, qid)
        # host wall of the staging calls up to their last enqueue (cuts,
        # puts and the waits that pace them, binning dispatches) — NOT
        # the completion of the device work they queue, which the caller
        # waits for on the handle
        self.last_bin_seconds = sp.seconds
        if _metrics.enabled():
            gbt_metrics()["phase"].observe(self.last_bin_seconds,
                                           engine="incore", phase="bin")
        return out

    def _stage_device_data(self, X, y, weight, cuts, sp,
                           qid=None) -> Dict[str, Any]:
        """:meth:`make_device_data`'s work, inside its ``dmlc.ingest``
        span ``sp``; every host phase is a child span."""
        p = self.param
        staged = None
        with span("dmlc.ingest.host_prep"):
            X = np.ascontiguousarray(X, dtype=np.float32)
            y = np.ascontiguousarray(y, dtype=np.float32)
            CHECK_EQ(len(y), X.shape[0], "X/y row mismatch")
            if p.objective.startswith("rank:"):
                CHECK(qid is not None, f"{p.objective} needs qid=")
                with span("dmlc.ingest.host_prep.regroup") as rs:
                    staged = self._regroup_ranking(X, y, np.asarray(qid),
                                                   weight, rs)
                X, y, weight = staged.X, staged.y, staged.weight
            else:
                CHECK(qid is None, f"qid= only valid for rank objectives "
                      f"(objective is {p.objective!r})")
            n, F = X.shape
            sp.set(features=F)
            cat = self._cat_flags(F)
            CHECK(cat is None or not _host_bin_requested(),
                  "DMLC_TPU_BIN_BACKEND=cpu bins against cut points only: "
                  "a categorical column (feature_types) is binned on the "
                  "device")
            self._settle_num_class(y)
            weight = self._fold_scale_pos_weight(y, weight)
            # the NaN scan reads the matrix where it lies: one about to
            # be put whole for the cut sort is read on the device (in
            # .cuts below), any other here
            scan = "device" if cuts is None and self.cuts is None else "host"
            if scan == "host":
                with span("dmlc.ingest.host_prep.nan_scan", bytes=X.nbytes,
                          on="host"):
                    missing_share = self._settle_missing_mode(
                        *self._nan_facts_host(X), cuts)
                if cat is not None:
                    # (where the matrix is put whole the device tells:
                    # _cuts_and_tables)
                    codes = X[:, np.flatnonzero(cat)]
                    self._check_codes(
                        int(np.count_nonzero((codes < 0)
                                             | (codes != np.floor(codes)))),
                        float(codes.max(initial=0.0)))
        # explicit cuts always win (a caller injecting boundaries must
        # not be silently overridden by leftovers from an earlier or
        # failed fit); existing self.cuts are kept only when nothing is
        # passed, so repeated handles share one binning
        mat_sharding = NamedSharding(self.mesh, P("data", None))
        x_dev = None
        n_padded = (staged.n_padded if staged is not None
                    else n + ((-n) % self._pad_multiple()))
        ndev = device_count(self.mesh)
        sharded = ndev > 1 and self._sharded_ingest_ok()
        if cuts is not None:
            self.cuts = cuts
        elif self.cuts is None:
            # missing mode: n_bins-1 VALUE bins (cuts [F, n_bins-2]),
            # bin n_bins-1 reserved for NaN
            # the matrix put for the cut sort (in pieces past the
            # 2^32-byte cliff, on every path), then the summary and
            # merge enqueued
            with span("dmlc.ingest.cuts", bytes=X.nbytes):
                if sharded:
                    # a mesh whose rows are placed chip by chip: every
                    # row goes ONCE, to the chip that owns it, the chips
                    # sort the columns between them (compute_cuts(mesh=))
                    # and no chip holds the whole matrix.  Where the bin
                    # matrix's row shards are these (nothing placed by a
                    # ranking handle, binning on the device) they stay
                    # resident until binned; else they serve the cuts
                    # alone and the slab stream below puts its own
                    resident = ((staged is None or staged.place is None)
                                and not _host_bin_requested())
                    x_cuts = self._put_row_shards(
                        X, n_padded if resident else n + (-n) % ndev)
                    if resident:
                        x_dev = x_cuts
                elif (ndev == 1 and self._one_slab(n) and n_padded == n
                        and not _host_bin_requested()
                        and not self._mesh_spans_processes()):
                    # one chip, one slab: the matrix the cut sort reads
                    # is the slab the binning reads, so it is put ONCE
                    # (at 1,183,747 x 968 a second copy beside the sort's
                    # temporaries does not fit the chip by the compiler's
                    # figures: 4.27 + 8.79 + 4.27 GiB of 15.75, PERF.md
                    # section 6, PR 42)
                    x_cuts = x_dev = _put_matrix(X, mat_sharding)
                else:
                    # one chip and several slabs (or a mesh whose rows
                    # cannot be placed chip by chip): the cut sort's
                    # operand alone, whole on the default device as
                    # jnp.asarray laid it (same rows, same order: the
                    # cuts are the same bytes); the binning streams its
                    # own slabs below
                    x_cuts = _put_matrix(X, None)
                # the mode, which compute_cuts needs, from ONE scan of
                # the matrix just put: the host waits here until it has
                # landed, as the sort has to
                with span("dmlc.ingest.cuts.nan_scan", bytes=X.nbytes,
                          on="device"):
                    facts = self._nan_facts_device(
                        x_cuts, n, self.mesh if sharded else None)
                missing_share = self._settle_missing_mode(*facts, cuts)
                if cat is None:
                    self.cuts = compute_cuts(
                        x_cuts, p.n_bins - 1 if self._missing else p.n_bins,
                        weight=weight,
                        allgather_fn=self._maybe_allgather(),
                        missing=self._missing,
                        mesh=self.mesh if sharded else None, n_rows=n)
                else:
                    self.cuts = self._cuts_and_tables(
                        x_cuts, cat, weight, n,
                        self.mesh if sharded else None)
                del x_cuts
        sp.set(missing=int(self._missing), missing_share=missing_share,
               nan_scan=scan,
               cuts_sort=("none" if scan == "host" else
                          "features" if sharded else "whole"))
        # cut width is the mode's load-bearing invariant: a mismatch
        # (e.g. standard-shaped cuts= injected into a missing-mode
        # model) would silently shift the reserved NaN bin out of the
        # histogram and misread the top value bin as missing mass
        CHECK_EQ(int(self.cuts.shape[1]),
                 p.n_bins - (2 if self._missing else 1),
                 f"cuts width must be n_bins-{2 if self._missing else 1} "
                 f"for this model "
                 f"({'missing' if self._missing else 'standard'} mode)")
        # every compile-time constant of the round program is now
        # pinned (cuts mode, shapes, params) — start compiling it in
        # the background so XLA works while the binning + H2D staging
        # below runs (the cold-start overlap; _boost_binned joins).
        # With packing/bundling requested the layout (a compile-time
        # constant) is only known AFTER ingest, so the kick moves there.
        pack_wanted = ((_bin_pack_requested() or _feature_bundle_requested())
                       and not self._missing)
        if not pack_wanted:
            self._maybe_start_warmup(F, n_padded)
        with span("dmlc.ingest.pad"):
            # a resident matrix is padded where it lies
            X, y, mask, n_pad = self._pad_rows(
                X if x_dev is None else None, y, weight, staged)

        row_sharding = NamedSharding(self.mesh, P("data"))
        # DMLC_TPU_BIN_BACKEND=cpu (see _host_bin_requested) bins on the
        # host and uploads the uint8 result — 4× less transfer than
        # shipping f32 X to bin on device, at the price of host-side
        # searchsorted; opt-in, default (unset) is the device path.
        if sharded and x_dev is None:
            # SHARDED slab stream (cuts given or kept, a ranking
            # handle's placed rows, binning on the host): each chip
            # receives — and, on the device-bin route, bins — exactly
            # its own row slice, streamed slab by slab; the binned
            # matrix is never resident on a single device and its slabs
            # never staged through a global put.  Binning is
            # per-element and the final layout is the same
            # P(None, "data") block layout, so the result is
            # bit-identical to both fallback paths (pinned by
            # tests/test_multichip.py).
            bins_t = self._ingest_slabs_sharded(
                self._slab_stream(X), len(X), len(X), F)
        elif _host_bin_requested() or (self._missing
                                       and self._mesh_spans_processes()):
            # missing + process-spanning mesh ALWAYS bins on host:
            # jax's cross-process device_put consistency assert
            # compares the global array with == and NaN != NaN, so an
            # (identical) NaN-bearing f32 X trips it — the uint8 bin
            # matrix is NaN-free (and 4x smaller to ship).  A local
            # mesh inside a multi-process job keeps the device path.
            bins_t = jax.device_put(
                _host_bin_t(X, np.asarray(self.cuts),
                            missing=self._missing),
                NamedSharding(self.mesh, P(None, "data")))
        else:
            # the round program wants bins FEATURE-major ([F, n], rows on
            # lanes): the Pallas histogram kernel then reads its native
            # layout directly instead of re-transposing the matrix inside
            # every boosting round (a full HBM round-trip per round).
            # Large inputs stream through the chunked double-buffered
            # path so the full f32 matrix is never device-resident next
            # to its uint8 bins (see _bin_ingest_streamed).  What the
            # cut sort left resident (one chip's one slab; a mesh's row
            # shards, padded) is binned where it lies: on a mesh each
            # chip its own shard, no row moves.
            bins_t = self._bin_ingest_streamed(X, mat_sharding, x_dev)
        del x_dev
        layout = None
        if pack_wanted:
            from dmlc_core_tpu.parallel import collectives as coll2
            if coll2.world_size() > 1 or self._mesh_spans_processes():
                LOG("WARNING", "DMLC_BIN_PACK/DMLC_FEATURE_BUNDLE ignored: "
                    "multi-process mesh (layout decisions need a global "
                    "view of per-feature bin usage)")
            else:
                layout = self._compute_bin_layout(bins_t, F, n)
                if layout is not None:
                    bins_t = _pack_matrix_fn(self.mesh, layout)(bins_t)
        elif self._missing and (_bin_pack_requested()
                                or _feature_bundle_requested()):
            LOG("WARNING", "DMLC_BIN_PACK/DMLC_FEATURE_BUNDLE ignored: "
                "missing mode (the reserved NaN bin pins every feature "
                "at full width)")
        self._bin_layout = layout
        if pack_wanted and self._pending_warmup is None:
            # the deferred cold-start kick (see above): layout is now a
            # pinned compile-time constant of the round program
            self._maybe_start_warmup(F, n + n_pad)
        with span("dmlc.ingest.labels"):
            y_d = jax.device_put(y, row_sharding)
            w_d = jax.device_put(mask, row_sharding)
            # a ranking handle's group table: device arrays, made once
            rank = None if staged is None else jax.tree.map(
                lambda a, spec: jax.device_put(
                    a, NamedSharding(self.mesh, spec)),
                staged.table, self._obj.table_specs())
        if len(self.cuts.devices()) > 1:
            # the cuts the mesh computed are committed to it, and so
            # would be every array computed from them (a predict's bins
            # against the model's trees).  The model keeps them where a
            # one-device ingest leaves them — default device, uncommitted
            # — which takes them through the host: LAST, with every
            # staging call enqueued, the host waits here until the cuts
            # exist (the binning still runs)
            with span("dmlc.ingest.cuts_fetch"):
                self.cuts = jnp.asarray(np.asarray(self.cuts))
        out = {
            "bins_t": bins_t,
            "y_d": y_d,
            "w_d": w_d,
            "n": n,
            "n_padded": n + n_pad,
            "n_features": F,
            "layout": layout,
        }
        if staged is not None:
            out.update(rank=rank, rank_groups=staged.groups,
                       rank_pos=staged.pos)
        return out

    @staticmethod
    def _check_codes(bad: int, top: float) -> None:
        """Hold a table's categorical columns to codes: ``bad`` of their
        values are negative, fractional or NaN, ``top`` is the largest."""
        CHECK(bad == 0,
              f"a categorical column (feature_types 'c') holds whole-number "
              f"codes >= 0 in float32: {bad} values are negative, "
              f"fractional or NaN")
        CHECK(top < CAT_MAX_CODE,
              f"a categorical column holds codes 0..{CAT_MAX_CODE - 1}, "
              f"got {top:g}: that many levels are ids, not categories")

    def _cuts_and_tables(self, x_cuts: jax.Array, cat: Tuple[bool, ...],
                         weight, n: int, mesh: Optional[Mesh]) -> jax.Array:
        """The cut matrix of a table with categorical columns, from the
        matrix ``x_cuts`` as it lies on the device for the cut sort (with
        ``mesh``: in row shards, ``n`` rows of data): a category→bin
        table for every ``cat`` column, from its codes' counts over ALL
        rows (``ops.quantile.cat_tables``; host span
        ``dmlc.ingest.cats``, device scope ``dmlc.cats``), and quantile
        cuts for the others, whose sort runs over those columns alone.
        The codes are held to what a code is first: ONE scan where the
        matrix lies, two ``[Fc]`` vectors back."""
        p = self.param
        CHECK(self._maybe_allgather() is None,
              "categorical columns (feature_types) in a job of several "
              "worker processes are not supported: the codes' counts are "
              "summed over one process's mesh")
        with span("dmlc.ingest.cats", columns=sum(cat)):
            bad, top = jax.device_get(cat_scan(x_cuts, cat, mesh))
            self._check_codes(int(bad.sum()), float(top.max()))
            tables = cat_tables(x_cuts, cat, int(top.max()) + 1, p.n_bins,
                                n, mesh)
        cat_idx = np.flatnonzero(cat)
        num_idx = np.flatnonzero(~np.asarray(cat))
        cuts = jnp.zeros((len(cat), p.n_bins - 1), jnp.float32
                         ).at[cat_idx].set(tables)
        if len(num_idx):
            cuts = cuts.at[num_idx].set(compute_cuts(
                _take_columns_fn(tuple(map(int, num_idx)), mesh)(x_cuts),
                p.n_bins, weight=weight, mesh=mesh, n_rows=n))
        return cuts

    @staticmethod
    def _nan_facts_host(X: np.ndarray):
        """The three facts :meth:`_settle_missing_mode` decides from, read
        off ``X`` ON THE HOST: the remainder.  A first ingest (no
        ``cuts=``, no cuts on the model) puts its matrix whole for the
        cut sort and reads them there (:meth:`_nan_facts_device`); this
        source is left where no whole matrix goes to the device — a
        continued fit, an eval handle, ``cuts=`` passed.  ``np.isnan``
        writes a mask a quarter of the matrix's size and reads it back,
        single-threaded (ROADMAP S2a(i): one blocked pass would do)."""
        nan = np.isnan(X)
        has_nan = bool(nan.any())
        # counting the marks costs a twentieth of making them, and only
        # a matrix that has some pays it
        share = np.count_nonzero(nan) / max(nan.size, 1) if has_nan else 0.0
        del nan
        return has_nan, share, lambda: np.isfinite(X).any(axis=0)

    @staticmethod
    def _nan_facts_device(x: jax.Array, n_rows: Optional[int] = None,
                          mesh: Optional[Mesh] = None):
        """The same three facts from ONE scan of ``x`` where it lies on
        the device (``ops.quantile.nan_scan``): two ``[F]`` vectors come
        back.  With ``mesh``, ``x`` lies there in row shards, its first
        ``n_rows`` rows the data: every chip scans its own and one
        ``psum`` adds up (``mesh_nan_scan``).  The cells are summed in
        Python ints: a column's count fits int32, the matrix's need not
        (40M x 28 is over half of 2**31)."""
        if mesh is None:
            n_rows, scan = x.shape[0], nan_scan(x)
        else:
            scan = mesh_nan_scan(x, n_rows, mesh)
        nan_count, finite_any = jax.device_get(scan)
        nan_cells = sum(map(int, nan_count))
        return (nan_cells > 0, nan_cells / max(n_rows * x.shape[1], 1),
                lambda: finite_any)

    def _settle_missing_mode(self, has_nan: bool, share: float, finite_any,
                             cuts) -> float:
        """Enter, keep or refuse missing mode from a scan's three facts:
        any NaN in the local rows, the share of cells that are NaN
        (returned), and — ``finite_any()``, asked only on entering the
        mode — per column whether it holds a finite value.  The ONE
        decision for both sources of the facts
        (:meth:`_nan_facts_device` where the matrix is put whole,
        :meth:`_nan_facts_host` elsewhere).  From the device source its
        errors come AFTER the matrix was put, where they used to come
        before any transfer; the array is dropped with the exception."""
        p = self.param
        # NaN = missing (XGBoost semantics): auto-enter missing mode on
        # first sight of NaN.  Sticky: once a model has missing-mode
        # cuts/trees, later NaN-free batches still bin in missing mode;
        # the reverse (NaN arriving at a non-missing model with cuts
        # already frozen) must fail loudly, not silently alias NaN into
        # the top value bin.
        from dmlc_core_tpu.parallel import collectives as coll
        if coll.world_size() > 1:
            # mode selection must be GLOBAL: a shard that happens to hold
            # no NaN rows would otherwise build differently-shaped cut
            # summaries (allgather shape mismatch) and a different round
            # program than its peers (histogram psum divergence)
            has_nan = bool(coll.allreduce(
                np.asarray([has_nan], np.int32), op="max")[0])
        CHECK(not has_nan or self._cat_flags() is None,
              "X contains NaN and feature_types names a categorical "
              "column: the missing mode has no set scan (a NaN code is no "
              "category, and the learned direction of a numeric column's "
              "NaN has no partition form) — impute, or give NaN a code")
        if has_nan and self.cuts is None and cuts is None:
            CHECK(p.n_bins >= 3,
                  "NaN features need n_bins >= 3 (one bin is reserved "
                  "for missing)")
            finite_any = finite_any()
            if coll.world_size() > 1:
                # per-feature finiteness must be judged globally too: a
                # shard whose rows happen to be all-NaN for one feature
                # must not fatal (false positive) while its peers walk
                # into the cut allgather without it
                finite_any = coll.allreduce(
                    finite_any.astype(np.int32), op="max").astype(bool)
            CHECK(finite_any.all(),
                  "a feature is all-NaN: drop it or impute")
            self._missing = True
        else:
            CHECK(not has_nan or self._missing,
                  "X contains NaN but this model's bins were built "
                  "without a missing bin — refit from scratch (NaN in "
                  "the first fit enables missing support) or impute")
        return float(share)

    def _compute_bin_layout(self, bins_t, n_features: int, n_valid: int
                            ) -> Optional["_bl.BinLayout"]:
        """Derive the packed/bundled storage layout from the device-
        resident bin matrix (``DMLC_BIN_PACK`` / ``DMLC_FEATURE_BUNDLE``).

        Per-feature occupancy comes from the BINNED DATA (per-bin
        occupancy counts over the real rows), not from the cuts: the
        quantile sketch's eps-bump makes cuts strictly increasing, so
        even a 2-valued feature carries full-width cuts AND spread-out
        bin ids — only the counts say how many bins a feature really
        uses (the layout compact-remaps those to dense ids) and which
        bin is its DEFAULT for bundling.  Bundle candidates are
        proposed on a host sample, then each is verified EXACTLY on the
        full device matrix (any row with ≥2 off-default members
        disqualifies the bundle) so the encode is lossless.  Returns
        None when no pair packs and no bundle fires — the round program
        then traces the untouched seed path."""
        p = self.param
        counts = _bl.bin_counts(bins_t, p.n_bins, n_valid)
        bundles: tuple = ()
        if _feature_bundle_requested():
            m = min(int(bins_t.shape[1]), 1 << 16)
            sample = np.asarray(jax.device_get(bins_t[:, :m]))
            if m > n_valid:
                sample = sample[:, :n_valid]
            proposed = _bl.detect_bundles(sample, counts, p.n_bins)
            dflt = _bl.default_bins(counts)
            bundles = tuple(
                b for b in proposed
                if self._bundle_exclusive(bins_t, b, dflt, n_valid))
            if len(proposed) != len(bundles):
                LOG("INFO", "feature bundling: %d/%d sampled bundles "
                    "survived exact full-data verification",
                    len(bundles), len(proposed))
        layout = _bl.compute_layout(counts, n_features, p.n_bins,
                                    pack=_bin_pack_requested(),
                                    bundles=bundles)
        if layout is not None:
            LOG("INFO", "bin layout: %d features -> %d physical rows "
                "(%d int4 pairs, %d bundles; %d/%d sync bins)",
                n_features, layout.phys_rows, len(layout.pairs),
                sum(1 for mm in layout.members if len(mm) > 1),
                layout.sync_bins, p.n_bins)
        return layout

    @staticmethod
    def _bundle_exclusive(bins_t, bundle, defaults, n_valid: int) -> bool:
        """Exact mutual-exclusivity check for one proposed bundle over
        the FULL device matrix: no real row may have two members off
        their DEFAULT (most frequent) bin or the shared-slot encode
        would collide.  Padding rows hold arbitrary bin ids and are
        masked out."""
        nz = jnp.zeros(bins_t.shape[1], jnp.int32)
        for f in bundle:
            nz = nz + (bins_t[int(f)] != int(defaults[int(f)])
                       ).astype(jnp.int32)
        valid = jnp.arange(bins_t.shape[1]) < n_valid
        return int(jax.device_get(jnp.max(jnp.where(valid, nz, 0)))) <= 1

    def _init_margin_device(self, n_padded: int) -> jax.Array:
        """Base-score margins created ON device (an np.full + device_put
        would ship n·4 bytes — 40 MB at 10M rows — for a constant the
        chip can materialize itself)."""
        p = self.param
        shape = self._margin_shape(n_padded)
        return _init_margin_fn(self.mesh, shape, p.base_score,
                               self._margin_spec())()

    def fit_device(
        self,
        device_data: Dict[str, Any],
        warmup_rounds: int = 0,
        chunk_callback: Optional[Any] = None,
        resume: bool = False,
    ) -> "HistGBT":
        """Boost ``n_trees`` rounds on a :meth:`make_device_data` handle
        — the repeated-fit fast path (no re-upload, no re-bin).

        Resets the ensemble (a new fit) unless ``resume=True``, which
        CONTINUES from the existing trees: the elastic-recovery resume
        path.  A resumed fit reuses the carried training margins when
        they match the handle (bit-identical to replay), else replays
        the ensemble's margins on device, and threads the global round
        index through so sampling draws match an uninterrupted run.
        The :meth:`fit`-only extras (eval_set / early stopping) are not
        available here; use :meth:`fit` for those.  A ``rank:*`` fit
        takes a handle made with ``make_device_data(qid=...)``: the
        group table is the handle's.
        ``chunk_callback(rounds_fetched, elapsed_s)`` fires as each
        dispatch chunk's trees arrive on host — incremental timing
        evidence for benchmark harnesses (bench.py's provisional
        emission rides this).
        """
        p = self.param
        rank = device_data.get("rank")
        if p.objective.startswith("rank:"):
            CHECK(rank is not None,
                  f"{p.objective}: the handle has no group table — make "
                  "it with make_device_data(X, y, qid=...)")
            # the handle knows its own groups, like its layout below
            self._obj = OBJECTIVES[p.objective](device_data["rank_groups"])
        # the handle knows its own storage layout — adopt it so the round
        # program matches the matrix even if another make_device_data ran
        # on this model in between
        self._bin_layout = device_data.get("layout")
        if self._pending_warmup is None:
            # no handle parked by make_device_data (or an earlier fit
            # consumed it): compile kfn + rem_fn concurrently now — a
            # warm _AOT_EXEC_CACHE makes this free, a warm persistent
            # cache makes it a disk read
            self._maybe_start_warmup(device_data["n_features"],
                                     device_data["n_padded"])
        if resume and self.trees:
            CHECK(self.cuts is not None, "resume-fit without cuts")
            n_prior = len(self.trees)
            preds = self._resume_margin_device(device_data)
        else:
            self.trees = []
            n_prior = 0
            preds = self._init_margin_device(device_data["n_padded"])
        self.best_iteration = None
        self.best_score = None
        self._early_stopped = False
        self._rank_pos = device_data.get("rank_pos")
        preds = self._boost_binned(
            device_data["bins_t"], device_data["y_d"], device_data["w_d"],
            preds, device_data["n_features"],
            warmup_rounds=warmup_rounds, chunk_callback=chunk_callback,
            round_offset=n_prior, rank=rank)
        self._train_preds = preds
        self._n_real_rows = device_data["n"]
        return self

    def _resume_margin_device(self, device_data: Dict[str, Any]) -> jax.Array:
        """Margins of the existing ensemble over the handle's rows.

        Prefers the carried training margins from the previous leg (the
        same buffer the round program produced — zero work); a restored
        process has none, so the trees replay on device instead.  Both
        routes are bit-identical: the replay applies the same leaf
        values in the same order the incremental updates added them.
        """
        n_padded = device_data["n_padded"]
        carried = self._train_preds
        if carried is not None and getattr(carried, "shape", (0,))[-1] == n_padded:
            return carried
        return self._replay_margin_device(device_data)

    def _replay_margin_device(self, device_data: Dict[str, Any]) -> jax.Array:
        """The existing ensemble's margins over a handle's rows, replayed
        on the device from the handle's own bins."""
        n_padded = device_data["n_padded"]
        CHECK(device_data.get("layout") is None,
              "resume-fit margin replay on a packed/bundled handle needs "
              "the carried training margins (a restored process has "
              "none) — refit, or make the handle with DMLC_BIN_PACK=0 "
              "and DMLC_FEATURE_BUNDLE=0")
        bins = _transpose_from_feature_major_fn(self.mesh)(
            device_data["bins_t"])
        init = self._init_margin_device(n_padded)
        tgt = init.sharding
        preds = self._apply_trees(bins, self._stacked_trees(self.trees),
                                  init)
        if preds.sharding != tgt:
            preds = jax.device_put(preds, tgt)
        return preds

    # ------------------------------------------------------------------
    # the round program
    # ------------------------------------------------------------------
    def _rank_loss(self, preds: jax.Array, rank) -> jax.Array:
        """Mean pairwise logistic loss of a ranking handle's margins:
        every shard's sums by its own part of the group table, then one
        ``psum`` (the training log's ``eval_every``)."""
        obj = self._obj
        kept = getattr(self, "_rank_loss_fn", None)
        if kept is None or kept[0] != obj.groups:
            def sums(preds_l, table_l):
                loss, count = obj.loss_sums(preds_l, table_l)
                return (jax.lax.psum(loss, "data")
                        / jnp.maximum(jax.lax.psum(count, "data"), 1))

            kept = self._rank_loss_fn = (obj.groups, jax.jit(shard_map(
                sums, mesh=self.mesh,
                in_specs=(P("data"), obj.table_specs()), out_specs=P(),
                check_vma=False)))
        return kept[1](preds, rank)

    def _round_plan(self, n_features: int, n_rows: int = 0) -> _RoundPlan:
        """Resolve every path choice of the round program from what can
        be observed before tracing — param, mesh, bin layout, knobs,
        backend, the (padded) rows it will be given — ONCE, and leave
        its record on ``self.round_plan``.
        The one place a lever is read: ``_round_fn_cache_key``,
        ``_build_round_fn`` and ``_boost_rounds``' accounting take the
        returned :class:`_RoundPlan` and consult nothing else, so a
        lever is added or dropped here and in the code it selects.

        ``hist_method`` lists the histogram engine of each BUILD in
        tree order: depth-wise, level 0 builds the root and level ℓ the
        ``2^(ℓ-1)`` left children; loss-guide builds one node at a time.
        ``hist_node_blocks`` / ``hist_feature_blocks`` record the blocks
        a Pallas build runs in where one kernel call does not take it
        (more than 32 nodes at 256 bins: ``max_depth`` >= 8; a matrix
        wider than 392 rows), as ``ops.build_histogram`` derives them
        again from the same shapes; ``hist_class_blocks`` the classes
        each kernel call of a build takes (a ``multi:*`` round: the
        stack of ``ops.hist_class_blocks``; ``[1]`` otherwise).  Every
        level is the staged descend + build + subtract: the one-kernel
        level it was measured against lost on the chip and went with PR
        47 (PERF.md section 6, PRs 45 and 47).  ``route_forms`` is each
        level's way to read a row's split: one packed word through a
        chain of selects where the parents are few and a device's rows
        many (``ops.table_select.route_form``; ``n_rows`` 0, a plan made
        without rows, reads the unpacked tables of a small fit)."""
        p = self.param
        depth = p.max_depth
        layout = self._bin_layout
        dsize = int(self.mesh.shape["data"])
        det_blocks = _hist_blocks(dsize)
        sync_bins = layout.sync_bins if layout is not None else p.n_bins
        mat_rows = layout.phys_rows if layout is not None else n_features
        lossguide = p.grow_policy == "lossguide"
        leaves = self._lossguide_leaves() if lossguide else 0
        cat_bins = self._cat_bins() if self._cat_flags(n_features) else ()
        if cat_bins:
            # what a set-valued split has no form for yet, said where the
            # plan is made, before anything is traced (PERF.md section 7)
            CHECK(not lossguide,
                  "grow_policy='lossguide' with categorical features "
                  "(feature_types) is not supported: the expansion descend "
                  "routes by threshold — use depthwise")
            CHECK(not any(int(v) for v in p.monotone_constraints),
                  "monotone_constraints with categorical features "
                  "(feature_types) are not supported: a partition has no "
                  "order for the bound to follow")
            CHECK(not self._missing,
                  "categorical features (feature_types) in missing mode "
                  "are not supported: the set scan has no missing "
                  "direction")
            CHECK(layout is None,
                  "DMLC_BIN_PACK / DMLC_FEATURE_BUNDLE with categorical "
                  "features (feature_types) are not supported: a packed "
                  "or bundled bin has no place in a node's set")
        CHECK(lossguide or depth >= 1,
              "max_depth=0 (no depth cap) needs grow_policy='lossguide' "
              "and a max_leaves budget; depthwise growth needs "
              "max_depth >= 1")
        builds = [1] if lossguide else (
            [1] + [1 << (lv - 1) for lv in range(1, depth)])
        packed = layout is not None and bool(layout.pairs)
        route = () if lossguide else tuple(
            route_form(1 << (lv - 1), n_rows // dsize)
            for lv in range(1, depth))
        route_word = (None if all(f == "tables" for f in route) else
                      SplitWord.of(n_features, p.n_bins, self._missing))
        methods = tuple(
            resolve_hist_method(p.hist_method, sync_bins, mat_rows, nb,
                                whole=packed)
            for nb in builds)
        # a packed layout is cut on nodes, never on features
        node_blocks = tuple(
            () if m != "pallas" else
            hist_node_blocks(sync_bins, mat_rows, nb, whole=packed)
            for m, nb in zip(methods, builds))
        plan = _RoundPlan(
            n_features=n_features,
            hist_method=methods,
            hist_feature_blocks=tuple(
                () if m != "pallas" else
                (mat_rows,) if packed else
                hist_feature_blocks(sync_bins, mat_rows, nbs[0])
                for m, nbs in zip(methods, node_blocks)),
            hist_node_blocks=node_blocks,
            missing=self._missing,
            pallas_interpret=pallas_interpret(),
            grow_policy="lossguide" if lossguide else "depthwise",
            max_leaves=leaves,
            layout=layout,
            hist_blocks=det_blocks,
            mesh_devices=dsize,
            rank=getattr(self._obj, "groups", None),
            num_class=p.num_class,
            hist_class_blocks=tuple(
                () if m != "pallas" else
                hist_class_blocks(sync_bins, mat_rows, nb,
                                  _class_batches(p.num_class)[1],
                                  whole=packed)
                for m, nb in zip(methods, builds)),
            route_forms=route, route_word=route_word,
            # rows clustered by leaf + tile-skipping builds: ONE tree a
            # round (K trees would need K orderings of the matrix) on the
            # plain matrix through the Pallas kernel, rows free to move
            # (deterministic blocks fold FIXED row ranges), and enough
            # leaves and rows a device, on few enough features, to pay
            # for the sort
            recluster_at=(
                recluster_points(leaves, n_rows // dsize, n_features)
                if lossguide and methods[0] == "pallas"
                and p.num_class <= 1 and layout is None and not det_blocks
                else ()),
            cat_bins=cat_bins)
        self.round_plan = plan.describe()
        if lossguide:
            # rows a device hands each build: the bound before a fit (all
            # of them, the other leaves masked out) — a fit that clusters
            # its rows writes what its last tree's kernels computed
            # (``_boost_rounds``).  A record, not of the plan
            self.round_plan["hist_rows_per_build"] = n_rows // dsize
        return plan

    def _lossguide_leaves(self) -> int:
        """Leaves of a loss-guide tree, and what the policy refuses —
        said where the plan is made, before anything is traced."""
        p = self.param
        CHECK(not self._missing,
              "grow_policy='lossguide' with NaN/missing features is not "
              "supported yet — impute, or use depthwise")
        CHECK(not any(int(v) for v in p.monotone_constraints),
              "grow_policy='lossguide' with monotone_constraints is not "
              "supported (bound propagation is level-order) — use "
              "depthwise")
        CHECK(p.max_leaves or p.max_depth,
              "grow_policy='lossguide' needs a bound: max_leaves, "
              "max_depth, or both (max_depth=0 and max_leaves=0 leave "
              "the tree unbounded)")
        caps = [v for v in (p.max_leaves,
                            (1 << p.max_depth) if p.max_depth else 0) if v]
        leaves = min(caps)
        CHECK(leaves >= 2,
              f"lossguide needs >= 2 leaves (max_depth={p.max_depth}, "
              f"max_leaves={p.max_leaves})")
        return leaves

    def _round_fn_cache_key(self, plan: _RoundPlan, n_rounds: int):
        """Everything baked into the traced round program as a constant:
        the mesh, the param's fields, the objective and ``plan``.

        Two HistGBT instances with equal keys trace to the SAME program,
        so the compiled executable is shared process-wide
        (``_ROUND_FN_CACHE``) instead of recompiled per instance —
        jax.jit's own cache is keyed on function identity, which a fresh
        per-instance closure always misses (~5 s/compile on a 1-core
        host, the dominant cost of small fits).
        """
        p = self.param
        obj = self._obj
        # registry objectives are per-name singletons (hashable as-is);
        # _PairwiseRank is configured per handle → key on its class (its
        # configuration, the group table's static part, is ``plan.rank``)
        obj_key = (type(obj).__name__ if isinstance(obj, _PairwiseRank)
                   else obj)
        mono = (tuple(int(v) for v in p.monotone_constraints)
                if p.monotone_constraints else None)
        return (self.mesh, n_rounds, p.max_depth, p.n_bins,
                p.learning_rate, p.reg_lambda, p.reg_alpha, p.gamma,
                p.min_child_weight, obj_key, mono, p.subsample,
                p.colsample_bytree, plan,
                (p.max_cat_to_onehot, p.max_cat_threshold)
                if plan.cat_bins else None)

    def _build_round_fn(self, plan: _RoundPlan, n_rounds: int = 1):
        """Jitted shard_map program running ``n_rounds`` boosting rounds
        (lax.scan); returns (new_preds, trees stacked [n_rounds, ...]).
        Built from the param and ``plan`` (:meth:`_round_plan`) alone."""
        cache_key = self._round_fn_cache_key(plan, n_rounds)
        cached = _ROUND_FN_CACHE.get(cache_key)
        if cached is not None:
            self._round_fn = cached
            return cached
        n_features = plan.n_features
        p = self.param
        depth = p.max_depth
        B = p.n_bins
        eta = p.learning_rate
        lam = p.reg_lambda
        alpha = p.reg_alpha
        gamma = p.gamma
        mcw = p.min_child_weight
        methods = plan.hist_method
        obj = self._obj
        n_leaf = 1 << depth            # (depth-wise's; loss-guide: node lists)
        half = max(n_leaf >> 1, 1)

        mono_arr = None
        if p.monotone_constraints:
            mc = np.asarray([int(v) for v in p.monotone_constraints],
                            np.int32)
            if np.any(mc):
                mono_arr = mc
        missing = plan.missing
        split_word = plan.route_word
        if missing:
            CHECK(mono_arr is None,
                  "monotone_constraints with NaN features is not "
                  "supported (learned missing direction would need "
                  "direction-aware bound propagation) — impute missing "
                  "values or drop the constraints")
        if alpha > 0.0:
            CHECK(mono_arr is None,
                  "monotone_constraints with reg_alpha is not supported "
                  "(the constrained gain evaluation would need the L1 "
                  "term at the clipped weights) — drop one of the two")
        # categorical columns: a split is a partition of a column's bins,
        # a row goes by membership of its bin in its node's set
        cat_bins = plan.cat_bins
        cat_how = ({"cat_bins": cat_bins,
                    "max_cat_to_onehot": p.max_cat_to_onehot,
                    "max_cat_threshold": p.max_cat_threshold}
                   if cat_bins else {})
        best_split = _make_best_split(B, lam, gamma, mcw, mono=mono_arr,
                                      missing=missing, alpha=alpha,
                                      **cat_how)
        best_split_leaf = _make_best_split(B, lam, gamma, mcw,
                                           with_child_sums=True,
                                           mono=mono_arr, missing=missing,
                                           alpha=alpha, **cat_how)
        if cat_bins:
            # per feature: is it categorical, and the bins it uses
            cat_flag = np.asarray(cat_bins) > 0
            cat_used = (np.arange(B)[None, :]
                        < np.asarray(cat_bins)[:, None])

            def route_sets(feat, thr, left_set):
                """What ``route`` reads of a level's splits: the
                threshold — ``B-1``, "every bin left", where the node
                splits a categorical feature — and the node's RIGHT set
                as bit words, the feature's bins outside the left set
                (empty where the node splits a numeric feature, or none):
                a row goes right if its bin is past the one OR in the
                other."""
                is_cat = jnp.asarray(cat_flag)[feat]
                right = (is_cat[:, None] & jnp.asarray(cat_used)[feat]
                         & ~left_set)
                return (jnp.where(is_cat, B - 1, thr),
                        set_words(right)[:, :plan.cat_words])

        # snapshot EVERY param the traced closure reads: the program is
        # cached process-wide under the key above, and a later retrace
        # (new input shape) must not see live mutations of some other
        # instance's param object
        subsample = p.subsample
        colsample = p.colsample_bytree
        sampling = subsample < 1.0 or colsample < 1.0
        # deterministic shard-invariant reduction (DMLC_HIST_BLOCKS, see
        # _hist_blocks): fixed global row blocks + fixed-order folds +
        # all_gather instead of psum, so the grown trees are
        # bit-identical across mesh shapes (the single-chip oracle)
        dsize = plan.mesh_devices
        det_blocks = plan.hist_blocks
        # packed/bundled storage layout (ops.binlayout): histograms are
        # built at [.., S, Bs] storage shape (smaller HBM reads + psum
        # payload), then unbundled back to [.., F, B] for split
        # evaluation, so split decisions — and save_model bytes — are
        # untouched.  None traces the exact seed program.
        layout = plan.layout
        lossguide = plan.grow_policy == "lossguide"
        recluster_at = plan.recluster_at
        if lossguide:
            L_leaves = plan.max_leaves
            # the open-leaf histogram pool is the policy's working set:
            # L·2·F·B f32 — refuse silently absurd configs up front
            CHECK(L_leaves * 2 * n_features * B * 4 <= (256 << 20),
                  f"lossguide histogram pool would exceed 256 MB "
                  f"({L_leaves} leaves x {n_features} features x {B} "
                  f"bins) — lower max_leaves or max_depth")

        def row_blocks(n_local):
            """The row blocks a shard's rows are cut into in
            deterministic mode, each as the index of its columns in a
            per-row array (``[n]``, ``[K, n]`` or the ``[F, n]`` matrix);
            none otherwise.  Blocked mode needs every shard's rows to
            split into whole fixed-size blocks; _pad_rows guarantees it
            for fit paths; a ranking handle on a mesh (a shard is whole
            queries) falls back where its rows do not divide."""
            c_local = det_blocks // dsize if det_blocks else 0
            if not c_local or n_local % c_local:
                return []
            rb = n_local // c_local
            return [(Ellipsis, slice(j * rb, (j + 1) * rb))
                    for j in range(c_local)]

        def hist_sync(x, n_blk):
            """Histogram-sync allreduce over the data axis: a plain
            psum normally; in deterministic mode an all_gather (no
            arithmetic) + the same fixed-order fold the per-shard
            partials used, so total = the one mesh-invariant tree."""
            if not n_blk:
                return jax.lax.psum(x, "data")
            if dsize == 1:
                return x
            gathered = jax.lax.all_gather(x, "data")       # [dsize, ...]
            return _tree_fold([gathered[i] for i in range(dsize)])

        def sample_masks(key, row_shape):
            """(row keep mask | None, feature mask | None) for one round."""
            keep = feat_mask = None
            key_rows, key_cols = jax.random.split(key)
            if subsample < 1.0:
                # decorrelate row draws across shards; the tree built
                # this round sees only the subsample (XGBoost
                # semantics: leaf values come from the subsample too)
                key_rows = jax.random.fold_in(
                    key_rows, jax.lax.axis_index("data"))
                keep = jax.random.uniform(key_rows, row_shape) < subsample
            if colsample < 1.0:
                # same mask on every shard (key NOT folded); exact
                # count like XGBoost: keep the ⌈c·F⌉ smallest scores
                n_keep = max(1, int(np.ceil(colsample * n_features)))
                scores = jax.random.uniform(key_cols, (n_features,))
                kth = jnp.sort(scores)[n_keep - 1]
                feat_mask = scores <= kth
            return keep, feat_mask

        def grow_tree(bins_tl, g, h, feat_mask):
            """One level-wise tree on (g, h) → (tree arrays, margin delta).

            The per-level histogram is psum'd over the data axis (THE
            histogram-sync allreduce); leaf g/h sums come free from the
            deepest level's cumsum.  With monotone constraints, every
            level additionally gets the chosen split's child sums so
            each node's weight bounds propagate down (child bound =
            midpoint of the clipped child weights, XGBoost-style) and
            the final leaf weights are clipped into their bounds.

            Sibling subtraction: below the root only LEFT children get a
            built histogram (right-child rows one-hot to nothing); the
            right child is parent − left from the previous level's
            already-synced histogram.  Halves the one-hot matmul height
            AND the psum bytes per level, and the subtraction itself is
            exact in f32 up to one rounding.  A level is staged:
            ``ops.descend_histogram`` (an XLA descend, then the
            histogram build), the sync, the subtraction.

            A CLASS axis: ``g`` / ``h`` ``[K, n]`` (a multiclass round)
            grow the K trees level by level TOGETHER — one tree's
            program, every per-class piece batched (``per_class``) inside
            the level's own scopes, and a level's histograms ONE
            ``build_histogram`` of K classes; every array of the result
            leads with K.  Class c's tree and delta are, bit for bit,
            this function's on ``(g[c], h[c])``."""
            per_class = _per_class(g)
            node = jnp.zeros(g.shape, jnp.int32)
            rows = row_blocks(int(bins_tl.shape[1]))
            n_blk = len(rows)

            def with_siblings(parent, left):
                """Both children's histograms, interleaved: the right
                child is its parent less the built left child."""
                right = parent - left
                return jnp.stack([left, right], axis=2).reshape(
                    2, 2 * parent.shape[1], left.shape[2], left.shape[3])

            def child_bounds(bounds, cg_, ch_, feat, thr):
                """A level's weight bounds handed down to its children
                (monotone constraints)."""
                n_nodes = bounds.shape[0]
                lo, hi = bounds[:, 0], bounds[:, 1]                   # [N]
                w_child = jnp.clip(
                    (-cg_ / (ch_ + lam)).reshape(n_nodes, 2),
                    lo[:, None], hi[:, None])
                mid = w_child.mean(axis=1)                            # [N]
                c = jnp.asarray(mono_arr)[feat]                       # [N]
                real = thr < B - 1               # degenerate splits inert
                up_l = jnp.where((c > 0) & real,
                                 jnp.minimum(hi, mid), hi)
                lo_r = jnp.where((c > 0) & real,
                                 jnp.maximum(lo, mid), lo)
                lo_l = jnp.where((c < 0) & real,
                                 jnp.maximum(lo, mid), lo)
                up_r = jnp.where((c < 0) & real,
                                 jnp.minimum(hi, mid), hi)
                return jnp.stack([
                    jnp.stack([lo_l, up_l], 1),
                    jnp.stack([lo_r, up_r], 1)], axis=1
                ).reshape(2 * n_nodes, 2)

            def leaf_tail(feat, thr, dirv, node, gsum, hsum, bounds,
                          right_words=None):
                """The leaf values and each row's leaf: the final descend
                (the loop's levels advanced node only up to level
                depth-1); shared gather-free feature select."""
                feat_sel = table_select(feat, node, 1 << (depth - 1))
                thr_sel = table_select(thr, node, 1 << (depth - 1))
                row_bin = select_feature_bins(bins_tl, feat_sel,
                                              layout=layout)             # [n]
                go_right = row_bin > thr_sel
                if right_words is not None:
                    with jax.named_scope("dmlc.round.leaf.route.cat"):
                        go_right = go_right | set_select(
                            right_words, node, row_bin, 1 << (depth - 1))
                if missing:
                    dir_sel = table_select(dirv, node, 1 << (depth - 1))
                    go_right = jnp.where(row_bin == B - 1, dir_sel == 0,
                                         go_right)
                node = 2 * node + go_right.astype(jnp.int32)
                leaf_w = -_maybe_l1(gsum, alpha) / (hsum + lam)
                if mono_arr is not None:
                    leaf_w = jnp.clip(leaf_w, bounds[:, 0], bounds[:, 1])
                return leaf_w * eta, node

            feats = []
            thrs = []
            gains = []
            dirs = []                                # missing mode only
            cats = []                      # categorical columns only
            gsum = hsum = None
            prev_hist = None
            # thr_rt, right_words: what route reads of a level's splits
            # (thr itself, and nothing, where no column is categorical)
            feat = thr = thr_rt = dirv = right_words = None
            bounds = None
            if mono_arr is not None:
                bounds = jnp.stack([jnp.full(1, -jnp.inf, jnp.float32),
                                    jnp.full(1, jnp.inf, jnp.float32)], 1)
                # every class's root unbounded: [(K,) 1, 2]
                bounds = jnp.broadcast_to(bounds,
                                          g.shape[:-1] + bounds.shape)
            for level in range(depth):
                n_nodes = 1 << level
                # the level's device phases (doc/observability.md), as
                # decorators of the calls that trace them: .route (each
                # row's node's split), .hist (the kernel, the level's
                # descend and sibling subtraction included), .sync, .split
                in_route, in_hist, in_sync, in_split = (
                    jax.named_scope(f"dmlc.round.L{level}.{phase}")
                    for phase in ("route", "hist", "sync", "split"))
                # inside them, the categorical columns' own: the set
                # lookup of .route, the sort and the scan of .split
                in_route_cat = jax.named_scope(
                    f"dmlc.round.L{level}.route.cat")
                split_how = ({"scope": partial(
                    jax.named_scope, f"dmlc.round.L{level}.split.cat")}
                    if cat_bins else {})
                if level == 0:
                    if n_blk:
                        hist = in_hist(_tree_fold)([
                            in_hist(build_histogram)(
                                bins_tl[sl], node[sl], g[sl], h[sl],
                                1, B, methods[0], transposed=True,
                                layout=layout)
                            for sl in rows])
                    else:
                        hist = in_hist(build_histogram)(
                            bins_tl, node, g, h, 1, B, methods[0],
                            transposed=True, layout=layout)
                    hist = in_sync(hist_sync)(hist, n_blk)
                else:
                    n_prev = n_nodes >> 1
                    form = plan.route_forms[level - 1]
                    if form == "tables":
                        select = in_route(per_class(
                            partial(table_select, n_entries=n_prev)))
                        feat_sel = select(feat, node)                 # [n]
                        thr_sel = select(thr_rt, node)                # [n]
                        dir_sel = select(dirv, node) if missing else None
                    else:
                        # ONE packed word a node, looked up once a row
                        lookup = (chain_select if form == "chain"
                                  else table_select)
                        word = in_route(split_word.pack)(feat, thr_rt, dirv)
                        word_sel = in_route(per_class(partial(
                            lookup, n_entries=n_prev)))(word, node)   # [n]
                        feat_sel, thr_sel, dir_sel = in_route(
                            split_word.unpack)(word_sel)
                    go_right = None
                    if cat_bins:
                        # the descend's own select, then membership of the
                        # row's bin in its node's right set
                        row_bin = in_hist(per_class(partial(
                            select_feature_bins, bins_tl)))(feat_sel)
                        go_right = in_route_cat(per_class(
                            lambda ws, nd, rb, ts: (rb > ts) | set_select(
                                ws, nd, rb, n_prev)))(
                                    right_words, node, row_bin, thr_sel)
                    if n_blk:
                        lefts, nodes2 = [], []
                        for sl in rows:
                            l_j, nd_j = in_hist(descend_histogram)(
                                bins_tl[sl], node[sl], feat_sel[sl],
                                thr_sel[sl], g[sl], h[sl],
                                n_prev, B, methods[level],
                                dir_sel=(None if dir_sel is None
                                         else dir_sel[sl]),
                                miss_bin=B - 1 if missing else None,
                                layout=layout,
                                go_right=(None if go_right is None
                                          else go_right[sl]))
                            lefts.append(l_j)
                            nodes2.append(nd_j)
                        left = in_hist(_tree_fold)(lefts)
                        node = jnp.concatenate(nodes2, axis=-1)
                    else:
                        left, node = in_hist(descend_histogram)(
                            bins_tl, node, feat_sel, thr_sel, g, h,
                            n_prev, B, methods[level],
                            dir_sel=dir_sel,
                            miss_bin=B - 1 if missing else None,
                            layout=layout, go_right=go_right)
                    left = in_sync(hist_sync)(left, n_blk)
                    hist = in_hist(per_class(with_siblings))(prev_hist,
                                                             left)
                # sibling subtraction stays in STORAGE space (prev_hist);
                # split evaluation sees original-feature space (identity
                # when layout is None)
                prev_hist = hist
                hist = in_split(per_class(partial(
                    _bl.unbundle_hist, layout=layout, n_bins=B)))(hist)
                if mono_arr is not None or level == depth - 1:
                    split = in_split(per_class(
                        lambda hh, bb: best_split_leaf(hh, feat_mask, bb,
                                                       **split_how)))
                    if missing:
                        feat, thr, dirv, gn, cg_, ch_ = split(hist, bounds)
                    elif cat_bins:
                        feat, thr, left_set, gn, cg_, ch_ = split(hist,
                                                                  bounds)
                    else:
                        feat, thr, gn, cg_, ch_ = split(hist, bounds)
                    if level == depth - 1:
                        gsum, hsum = cg_, ch_
                else:
                    split = in_split(per_class(
                        lambda hh: best_split(hh, feat_mask, **split_how)))
                    if missing:
                        feat, thr, dirv, gn = split(hist)
                    elif cat_bins:
                        feat, thr, left_set, gn = split(hist)
                    else:
                        feat, thr, gn = split(hist)
                thr_rt = thr
                if cat_bins:
                    thr_rt, right_words = in_split(per_class(route_sets))(
                        feat, thr, left_set)
                    cats.append(in_split(per_class(lambda m: jnp.pad(
                        set_words(m), ((0, half - n_nodes), (0, 0)))))(
                            left_set))
                # pad per-level arrays to a common width for stacking
                pad_n = per_class(
                    lambda a: jnp.pad(a, (0, half - n_nodes)))
                feats.append(pad_n(feat))
                thrs.append(pad_n(thr))
                gains.append(pad_n(gn))
                if missing:
                    dirs.append(pad_n(dirv))
                if mono_arr is not None:
                    bounds = per_class(child_bounds)(bounds, cg_, ch_,
                                                     feat, thr)
            with jax.named_scope("dmlc.round.leaf"):
                leaf, node = per_class(leaf_tail)(
                    feat, thr_rt, dirv, node, gsum, hsum, bounds,
                    right_words)
                # the levels' tables side by side: [(K,) depth, half]
                tree = {
                    "feat": jnp.stack(feats, axis=-2),
                    "thr": jnp.stack(thrs, axis=-2),
                    "gain": jnp.stack(gains, axis=-2),
                    "leaf": leaf,                        # [(K,) n_leaf]
                }
                if missing:
                    tree["dir"] = jnp.stack(dirs, axis=-2)
                if cat_bins:
                    # every node's LEFT set as bit words (a numeric
                    # split's: its bins <= thr): [(K,) depth, half, B/32]
                    tree["cats"] = jnp.stack(cats, axis=-3)
                return tree, per_class(partial(
                    table_select, n_entries=n_leaf))(leaf, node)

        def grow_tree_lossguide(bins_tl, g, h, feat_mask):
            """One LEAF-WISE tree on (g, h) → (node list, margin delta).

            XGBoost's ``grow_policy=lossguide`` (LightGBM's growth): a
            gain-priority queue over the open leaves; each of the
            ``L_leaves - 1`` expansions splits the open leaf of highest
            candidate gain (ties: the LOWEST node id, ``argmax``'s first),
            builds ONE histogram — the left child, every other row masked
            out — and takes the right sibling by subtraction from the
            parent's pooled histogram.  A leaf is split only while its
            gain > ``gamma`` and both children hold ``min_child_weight``
            of hessian (``best_split``'s own gates), so once no open leaf
            qualifies every later expansion is a no-op and the tree has
            fewer leaves than its budget.

            The tree is a NODE LIST of ``2 * L_leaves - 1`` entries, of
            any depth: node 0 is the root and expansion ``k`` makes nodes
            ``2k+1`` (left) and ``2k+2`` (right).  ``feat`` / ``thr`` /
            ``gain`` hold a split node's split (a leaf's and an unused
            entry's are the degenerate 0 / B-1 / 0), ``left`` / ``right``
            its children (-1: none), ``value`` a leaf's weight times eta
            (0 elsewhere).  A depth cap (``max_depth`` > 0) is a test on
            a node's depth.  With a budget of ``2^max_depth`` leaves the
            split STRUCTURE is depth-wise's, bit for bit — pinned by
            tests/test_lossguide.py; leaf values agree to f32 rounding
            (subtracted vs freshly-built deepest-level histograms).

            Deterministic mode (DMLC_HIST_BLOCKS) uses the same
            per-block build + fixed-order fold + all_gather combine as
            depthwise, and the expansion order derives only from synced
            gains — so mesh-shape invariance survives.

            A CLASS axis (``g`` / ``h`` ``[K, n]``): the K trees expand
            in step — each class its own queue, leaf and split, the
            pieces batched (``per_class``) — and an expansion's K
            builds are ONE ``build_histogram`` of K classes.

            CLUSTERED rows (``plan.recluster_at``, where
            ``HistGBT._round_plan`` engages it): the matrix and the row
            vectors are made tile-aligned ONCE (``ops.tile_aligned``),
            the expansion scan is cut at the plan's points, and at each
            the device's rows are re-ordered by the leaf they sit in
            (``recluster``); every build says which tiles hold a row of
            its node (``ops.tile_liveness``) and the kernel skips the
            others, so a build after the point costs what its leaf's
            cluster costs.  The queue, the order of expansions, the
            built (left) child, the gates and each node's rows are what
            they were: only the order in which a histogram's float32
            tile sums are added changes.  The per-row delta comes back
            in input order.  The tree carries ``hist_tiles``, the tiles
            its builds computed, summed over the devices, which
            ``_boost_rounds`` takes out again."""
            per_class = _per_class(g)
            n_local = int(bins_tl.shape[1])
            rows = row_blocks(n_local)
            n_blk = len(rows)
            M = 2 * L_leaves - 1                  # the node list's entries
            # the expansion's device phases (doc/observability.md)
            in_pick, in_hist, in_settle, in_recluster = (
                jax.named_scope(f"dmlc.round.expand.{phase}")
                for phase in ("pick", "hist", "settle", "recluster"))
            clustered = bool(recluster_at)

            def build_one(data, node_build, tile_live=None):
                """Histogram of the single node whose rows have
                ``node_build == 0`` (everything else -1) over the rows
                ``data`` = (bins, g, h), synced."""
                bins_x, g_x, h_x = data
                if n_blk:
                    hh = _tree_fold([
                        build_histogram(
                            bins_x[sl], node_build[sl], g_x[sl], h_x[sl],
                            1, B, methods[0], transposed=True,
                            layout=layout)
                        for sl in rows])
                else:
                    # clustered: tile-aligned operands, dead tiles skipped
                    how = ({"tile_live": tile_live, "n_features": n_features}
                           if clustered else {"layout": layout})
                    hh = build_histogram(bins_x, node_build, g_x, h_x, 1, B,
                                         methods[0], transposed=True, **how)
                return hist_sync(hh, n_blk)      # [(K,) 2, 1, S, Bs]

            def eval_nodes(hist_st):
                """(feat, thr, gain, tot_g, tot_h) per node of a synced
                STORAGE-space histogram stack [2, N, S, Bs]."""
                ev = _bl.unbundle_hist(hist_st, layout, B)
                f_, t_, gn_, _, _ = best_split_leaf(ev, feat_mask)
                tot = jnp.cumsum(ev, axis=-1)[..., 0, -1]    # [2, N]
                return f_, t_, gn_, tot[0], tot[1]

            tabs = (_bl.layout_tables(layout) if layout is not None
                    else None)

            def row_bins_of(bins_x, fsel):
                """Bins of ONE (traced-scalar) original feature for every
                local row — the expansion descend's read."""
                if layout is None:
                    row = jax.lax.dynamic_slice_in_dim(bins_x, fsel, 1, 0)
                    return row[0].astype(jnp.int32)
                src_f = jnp.asarray(tabs["src"][tabs["owner"]])
                nib_f = jnp.asarray(tabs["nib"][tabs["owner"]])
                row = jax.lax.dynamic_slice_in_dim(
                    bins_x, src_f[fsel], 1, 0)[0].astype(jnp.int32)
                nb = nib_f[fsel]
                v = jnp.where(nb == 1, row >> 4,
                              jnp.where(nb == 0, row & 15, row))
                if layout.has_bundles:
                    off = jnp.asarray(tabs["off"])[fsel]
                    wid = jnp.asarray(tabs["wid"])[fsel]
                    bnd = jnp.asarray(tabs["bundled"])[fsel]
                    in_seg = (v >= off) & (v < off + wid - 1)
                    v = jnp.where(bnd,
                                  jnp.where(in_seg, v - off + 1, 0), v)
                if tabs["any_remap"]:
                    # compact id → original bin id (thresholds are
                    # original-space): orig = occ_pad[fsel, v]
                    occ_row = jnp.asarray(tabs["occ_pad"])[fsel]
                    orig = jnp.zeros_like(v)
                    for k in range(_bl.PACK_WIDTH):
                        orig = orig + jnp.where(v == k, occ_row[k], 0)
                    v = jnp.where(jnp.asarray(tabs["remap"])[fsel],
                                  orig, v)
                return v

            def open_root(root):
                """The queue with the root (node 0, pool slot 0) alone in
                it."""
                f0, t0_, g0, tg0, th0 = eval_nodes(root)
                zi = jnp.zeros(M, jnp.int32)
                zf = jnp.zeros(M, jnp.float32)
                return {
                    "open": jnp.zeros(M, bool).at[0].set(True),
                    "leaf_g": zf.at[0].set(tg0[0]),
                    "leaf_h": zf.at[0].set(th0[0]),
                    "cand_feat": zi.at[0].set(f0[0]),
                    "cand_thr": jnp.full(M, B - 1, jnp.int32).at[0].set(
                        t0_[0]),
                    "cand_gain": jnp.full(M, -jnp.inf, jnp.float32).at[
                        0].set(g0[0]),
                    "depth": zi,
                    # a node's pool slot: its parent's for a left child,
                    # slot k+1 for expansion k's right child
                    "slot": zi,
                    "pool": jnp.zeros((L_leaves,) + root[:, 0].shape,
                                      jnp.float32).at[0].set(root[:, 0]),
                    "feat": zi, "thr": jnp.full(M, B - 1, jnp.int32),
                    "gain": zf, "left": jnp.full(M, -1, jnp.int32),
                    "right": jnp.full(M, -1, jnp.int32),
                }

            def pick(node, st, k, bins_x):
                """Expansion ``k``: the open leaf to split and its rows
                sent down to nodes ``2k+1`` / ``2k+2``; the left child's
                rows to build (clustered: and the tiles that hold one),
                and what :func:`settle` needs of the choice."""
                # priority queue: best candidate gain among the open
                # leaves.  A real split always has recorded gain > gamma
                # (best_split's own split_ok gate), so the > gamma test
                # is exactly depthwise's expansion rule.
                gains = jnp.where(st["open"], st["cand_gain"], -jnp.inf)
                hc = jnp.argmax(gains).astype(jnp.int32)  # ties: lowest id
                ok = gains[hc] > gamma
                fsel = st["cand_feat"][hc]
                tsel = st["cand_thr"][hc]
                lc, rc = 2 * k + 1, 2 * k + 2
                # descend the expanded leaf's rows on (fsel, tsel)
                v = row_bins_of(bins_x, fsel)
                go_right = v > tsel
                mine = ok & (node == hc)
                node = jnp.where(mine, jnp.where(go_right, rc, lc), node)
                # ONE build: left child only; right = parent − left
                node_build = jnp.where(mine & ~go_right, 0, -1)
                live = tile_liveness(node_build) if clustered else None
                return node, node_build, live, (hc, ok, fsel, tsel, lc, rc)

            def settle(st, picked, left, k):
                """The expansion's two children into the queue and the
                pool, from the built left child ``left`` [2, 1, S, Bs]."""
                hc, ok, fsel, tsel, lc, rc = picked
                left = left[:, 0]                         # [2, S, Bs]
                slot = st["slot"][hc]
                right = st["pool"][slot] - left
                f2, t2, g2, tg2, th2 = eval_nodes(
                    jnp.stack([left, right], axis=1))
                child_depth = st["depth"][hc] + 1
                if depth:                # children at the cap never expand
                    g2 = jnp.where(child_depth < depth, g2, -jnp.inf)
                # a skipped expansion (ok False) writes past the end
                hc_, lc_, rc_ = (jnp.where(ok, i, M) for i in (hc, lc, rc))
                st = dict(st)

                def put(name, at, value):
                    st[name] = st[name].at[at].set(value, mode="drop")

                put("open", hc_, False)
                put("feat", hc_, fsel)
                put("thr", hc_, tsel)
                put("gain", hc_, st["cand_gain"][hc])
                put("left", hc_, lc)
                put("right", hc_, rc)
                for j, at in enumerate((lc_, rc_)):
                    put("open", at, True)
                    put("leaf_g", at, tg2[j])
                    put("leaf_h", at, th2[j])
                    put("cand_feat", at, f2[j])
                    put("cand_thr", at, t2[j])
                    put("cand_gain", at, g2[j])
                    put("depth", at, child_depth)
                # the parent's slot → the left child, slot k+1 → the right
                put("slot", lc_, slot)
                put("slot", rc_, k + 1)
                put("pool", jnp.where(ok, slot, L_leaves), left)
                put("pool", jnp.where(ok, k + 1, L_leaves), right)
                return st

            def expander(data):
                """The expansion scan's body over the rows ``data``."""
                def expand(carry, k):
                    node, st, tiles = carry
                    node, node_build, live, picked = in_pick(per_class(
                        partial(pick, k=k, bins_x=data[0])))(node, st)
                    left = in_hist(build_one)(data, node_build, live)
                    st = in_settle(per_class(partial(settle, k=k)))(
                        st, picked, left)
                    if clustered:
                        tiles = in_pick(jnp.add)(tiles, live.sum())
                    return (node, st, tiles), None
                return expand

            def recluster(data, node, st, order, at):
                """The device's rows re-ordered by the node they sit in
                after ``at`` expansions (ids ``0 .. 2·at``; the id order
                is the clusters' order), each open leaf's rows by the
                side they take under its recorded candidate split — the
                split it gets if it is ever expanded, so its children's
                rows come out contiguous too.  Stable: equal keys keep
                their order, and two fits order alike.  ``order`` is
                each row's position in the input."""
                bins_x, g_x, h_x = data
                n_ids = 2 * at + 1
                feat_sel = table_select(st["cand_feat"][:n_ids], node, n_ids)
                thr_sel = table_select(st["cand_thr"][:n_ids], node, n_ids)
                side = select_feature_bins(bins_x, feat_sel) > thr_sel
                last = jnp.iinfo(jnp.int32).max          # the pad rows'
                key = jnp.where(node >= 0, 2 * node + side, last)
                if order is None:
                    order = jnp.arange(node.shape[0], dtype=jnp.int32)
                key, bins_x, g_x, h_x, order = recluster_rows(
                    key, bins_x, n_features, g_x, h_x, order)
                node = jnp.where(key == last, -1, key >> 1)
                return (bins_x, g_x, h_x), node, order

            def finish(node, st):
                """The node list and each row's delta."""
                w_all = (-_maybe_l1(st["leaf_g"], alpha)
                         / (st["leaf_h"] + lam)) * eta
                value = jnp.where(st["open"], w_all, 0.0)   # open = a leaf
                tree = {k: st[k] for k in ("feat", "thr", "gain", "left",
                                           "right")}
                tree["value"] = value
                return tree, table_select(value, node, M)

            # ---- root ----
            with jax.named_scope("dmlc.round.root"):
                node = jnp.zeros(g.shape, jnp.int32)     # node-list ids
                data, live, tiles = (bins_tl, g, h), None, None
                if clustered:
                    bins_x, node, g_x, h_x = tile_aligned(bins_tl, node,
                                                          g, h)
                    data, live = (bins_x, g_x, h_x), tile_liveness(node)
                    tiles = live.sum()
                st = per_class(open_root)(build_one(data, node, live))
            # the expansions, cut where the rows re-cluster (nowhere: one
            # scan over the rows as they came)
            order = None
            for start, stop in pairwise((0,) + recluster_at
                                        + (L_leaves - 1,)):
                if start:
                    data, node, order = in_recluster(recluster)(
                        data, node, st, order, start)
                (node, st, tiles), _ = jax.lax.scan(
                    expander(data), (node, st, tiles),
                    jnp.arange(start, stop, dtype=jnp.int32))
            if clustered:
                # each row's node back where the row came from
                node = in_recluster(lambda o, nd: jax.lax.sort(
                    (o, nd), num_keys=1, is_stable=False)[1][:n_local])(
                        order, node)
            with jax.named_scope("dmlc.round.leaf"):
                tree, delta = per_class(finish)(node, st)
                if clustered:
                    tree["hist_tiles"] = jax.lax.psum(tiles, "data")
                return tree, delta

        grow = grow_tree_lossguide if lossguide else grow_tree

        n_class = plan.num_class

        ranking = plan.rank is not None

        def round_body(bins_tl, y_l, w_l, preds_l, table_l=None, key=None):
            keep = feat_mask = None
            if sampling:
                keep, feat_mask = sample_masks(key, y_l.shape)
            if n_class <= 1:
                with jax.named_scope("dmlc.round.grad"):
                    g, h = (obj.grad_hess(preds_l, y_l, table_l) if ranking
                            else obj.grad_hess(preds_l, y_l))
                    g = g * w_l
                    h = h * w_l
                    if keep is not None:
                        g = jnp.where(keep, g, 0.0)
                        h = jnp.where(keep, h, 0.0)
                tree, delta = grow(bins_tl, g, h, feat_mask)
                with jax.named_scope("dmlc.round.update"):
                    return preds_l + delta, tree
            # multiclass: preds_l class-major [K, n] (rows on the lanes,
            # a class's margins one contiguous row); one tree per class
            # per round, built on the full-softmax gradients (XGBoost
            # multi:softmax), every class from the margins the round
            # STARTED with
            with jax.named_scope("dmlc.round.grad"):
                g_all, h_all = obj.grad_hess(preds_l, y_l)    # [K, n]
                g_all = g_all * w_l
                h_all = h_all * w_l
                if keep is not None:                      # same rows ∀ class
                    g_all = jnp.where(keep, g_all, 0.0)
                    h_all = jnp.where(keep, h_all, 0.0)

            # the class loop, batched: ONE tree's program with a class
            # axis, the K trees grown level by level together and a
            # level's histograms one kernel call per block of classes
            # (ops.hist_class_blocks); the trees are byte-identical to K
            # single-class ``grow``s
            with jax.named_scope("dmlc.round.class"):
                tree, deltas = _grow_classes(
                    grow, bins_tl, g_all, h_all, feat_mask)  # [K, ...], [K, n]
            with jax.named_scope("dmlc.round.update"):
                return preds_l + deltas, tree

        preds_spec = P(None, "data") if n_class > 1 else P("data")
        # a ranking handle's group table, then the sampling key, follow
        # the four arrays every fit has
        table_specs = (obj.table_specs(),) if ranking else ()

        def k_rounds_body(bins_tl, y_l, w_l, preds_l, *rest):
            table_l = rest[0] if ranking else None
            if sampling:
                def step(carry, _):
                    preds_c, key_c = carry
                    key_c, key_r = jax.random.split(key_c)
                    preds2, tree = round_body(bins_tl, y_l, w_l, preds_c,
                                              table_l, key_r)
                    return (preds2, key_c), tree

                (preds_out, _), trees = jax.lax.scan(
                    step, (preds_l, rest[-1]), None, length=n_rounds)
                return preds_out, trees

            def step(preds_c, _):
                return round_body(bins_tl, y_l, w_l, preds_c, table_l)

            return jax.lax.scan(step, preds_l, None, length=n_rounds)

        in_specs = ((P(None, "data"), P("data"), P("data"), preds_spec)
                    + table_specs + ((P(),) if sampling else ()))

        mapped = shard_map(
            k_rounds_body,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=(preds_spec, P()),
            check_vma=False,
        )
        self._round_fn = jax.jit(mapped, donate_argnums=(3,))
        _ROUND_FN_CACHE[cache_key] = self._round_fn
        return self._round_fn

    # ------------------------------------------------------------------
    # inference
    # ------------------------------------------------------------------
    #: rows per device batch in predict — bounds the transient f32 X and
    #: bin matrices on device regardless of input size (Criteo-scale
    #: scoring must not need training-scale memory)
    _PREDICT_BATCH = 2_000_000

    def _resolve_trees(self, n_trees: Optional[int]):
        """Trees used for prediction: explicit count, else the
        early-stop winner (XGBoost default), else all."""
        if n_trees is None and getattr(self, "_early_stopped", False) \
                and self.best_iteration is not None:
            n_trees = self.best_iteration + 1
        return self.trees if n_trees is None else self.trees[:n_trees]

    def _predict_stacked(self, X: np.ndarray, stacked, output_margin: bool,
                         op: span) -> np.ndarray:
        """Batched margin/transform over an already-stacked (device)
        forest — shared by predict and predict_iter so the streaming
        path uploads the model once.  One program a slab
        (:func:`_predict_slab`); ``op`` is the caller's ``dmlc.predict``
        span, whose ``programs`` counts the enqueues."""
        p = self.param
        X = np.ascontiguousarray(X, dtype=np.float32)
        self._check_nan_allowed(X, "predict")
        transform = None if output_margin else self._obj.transform
        if len(X) == 0:
            return self._no_rows(transform)
        miss_bin = self._miss_bin()
        # (no categorical column: the call, and its program, as they were)
        flags = self._cat_flags()
        cat = () if flags is None else (flags,)
        outs = []
        for lo in range(0, len(X), self._PREDICT_BATCH):
            t_b = get_time()
            xb = X[lo:lo + self._PREDICT_BATCH]
            with span("dmlc.predict.put", bytes=xb.nbytes):
                xb_d = jnp.asarray(xb)
            with span("dmlc.predict.dispatch", programs=1):
                out_d = _predict_slab(xb_d, self.cuts, stacked,
                                      p.max_depth, miss_bin, p.base_score,
                                      transform, *cat)
                del xb_d
            op.set(programs=op.counts["programs"] + 1)
            with span("dmlc.predict.fetch", bytes=out_d.nbytes):
                # the copy is asked for BEHIND the program, as
                # np.asarray alone would ask: a copy started only once
                # the wait is over costs a call 0.2 ms more (PERF.md §6)
                out_d.copy_to_host_async()
                with span("dmlc.predict.fetch.wait"):
                    out_d.block_until_ready()
                with span("dmlc.predict.fetch.copy", bytes=out_d.nbytes):
                    out = np.asarray(out_d)
                    # class-major on the device, [n, K] for the caller
                    outs.append(out if out.ndim == 1
                                else np.ascontiguousarray(out.T))
            if _metrics.enabled():
                # np.asarray above is a real fetch, so this wall delta
                # covers bin + tree apply + D2H for the batch
                gbt_metrics()["phase"].observe(get_time() - t_b,
                                               engine="incore",
                                               phase="predict")
        return np.concatenate(outs) if len(outs) > 1 else outs[0]

    def predict(self, X: np.ndarray, output_margin: bool = False,
                n_trees: Optional[int] = None) -> np.ndarray:
        CHECK(self.cuts is not None, "predict before fit")
        CHECK(len(self.trees) > 0, "no trees trained")
        with span("dmlc.predict", rows=len(X), programs=0) as op:
            stacked = self._stacked_trees(self._resolve_trees(n_trees))
            return self._predict_stacked(X, stacked, output_margin, op)

    def predict_iter(self, row_iter, output_margin: bool = False,
                     n_trees: Optional[int] = None,
                     batch_rows: int = _PREDICT_BATCH) -> np.ndarray:
        """Streaming prediction over a :class:`RowBlockIter` — the
        inference side of :meth:`fit_external` (a model trained
        out-of-core must also SCORE out-of-core; XGBoost predicts
        straight from a DMatrix).  CSR pages densify into a bounded
        ``batch_rows`` staging slab that flows through the same batched
        device path as :meth:`predict`; host memory holds one slab plus
        the output vector, never the dense matrix.

        The feature width is pinned by the trained cuts: pages whose
        column index exceeds it fail loudly (a silently truncated
        feature would score garbage)."""
        from dmlc_core_tpu.data.iter import iter_dense_slabs

        CHECK(self.cuts is not None, "predict before fit")
        CHECK(len(self.trees) > 0, "no trees trained")
        F = int(self.cuts.shape[0])
        # stack + upload the forest ONCE, not per slab (50 slabs at 50M
        # rows must not re-ship the model 50 times)
        with span("dmlc.predict", programs=0) as op:
            stacked = self._stacked_trees(self._resolve_trees(n_trees))
            outs = [self._predict_stacked(xb, stacked, output_margin, op)
                    for xb, _, _ in iter_dense_slabs(row_iter, F,
                                                     batch_rows)]
        if not outs:
            return self._no_rows(None if output_margin
                                 else self._obj.transform)
        return np.concatenate(outs) if len(outs) > 1 else outs[0]

    def _no_rows(self, transform) -> np.ndarray:
        """What ``predict`` answers for no rows: the shape of its answer
        for some (``[0, K]`` where a row's answer is K numbers)."""
        out = jax.eval_shape(
            lambda m: m if transform is None else transform(m),
            jax.ShapeDtypeStruct(self._margin_shape(0), np.float32))
        return np.zeros(out.shape[::-1], np.float32)

    def predict_leaf(self, X: np.ndarray,
                     n_trees: Optional[int] = None) -> np.ndarray:
        """Per-tree leaf assignment — XGBoost's ``pred_leaf=True``.

        Returns int32 ``[n, T]`` (multiclass: ``[n, T, K]``) of leaf
        positions in ``[0, 2^max_depth)`` — the index within each
        depth-complete tree's leaf layer (XGBoost's global node ids for
        a complete tree are ``leaf + 2^depth − 1``); for loss-guide
        trees, the leaf's id in the tree's node list (``[0, 2L-1)``).
        The classic use is GBDT feature embeddings (leaf one-hots into a
        linear model)."""
        CHECK(self.cuts is not None, "predict before fit")
        CHECK(len(self.trees) > 0, "no trees trained")
        depth = self.param.max_depth
        use = self._resolve_trees(n_trees)
        # exact-count stack (not the padded chunks): the output is
        # [n, T] leaf ids, so padded no-op trees would widen it
        keys = tuple(k for k in _forest_keys(use)
                     if k not in ("leaf", "value"))
        stacked = {k: jnp.asarray(np.stack([t[k] for t in use]))
                   for k in keys}
        X = np.ascontiguousarray(X, dtype=np.float32)
        self._check_nan_allowed(X, "predict_leaf")
        if len(X) == 0:
            shape = ((0, len(use), self.param.num_class)
                     if self.param.num_class > 1 else (0, len(use)))
            return np.zeros(shape, np.int32)
        miss = self._miss_bin()
        dirs, cats = stacked.get("dir"), stacked.get("cats")
        outs = []
        for lo in range(0, len(X), self._PREDICT_BATCH):
            bins = self._bin_matrix(
                jnp.asarray(X[lo:lo + self._PREDICT_BATCH]))
            if "left" in stacked:           # node lists: [T, (K,) M]
                outs.append(np.asarray(_node_list_leaves(bins, stacked)))
            elif stacked["feat"].ndim == 4:  # multiclass [T, K, depth, half]
                cols = [_leaf_indices(
                            bins, stacked["feat"][:, c],
                            stacked["thr"][:, c], depth,
                            dirs[:, c] if dirs is not None else None,
                            miss, cats[:, c] if cats is not None else None)
                        for c in range(stacked["feat"].shape[1])]
                outs.append(np.stack([np.asarray(c) for c in cols], axis=2))
            else:
                outs.append(np.asarray(
                    _leaf_indices(bins, stacked["feat"], stacked["thr"],
                                  depth, dirs, miss, cats)))
        return np.concatenate(outs) if len(outs) > 1 else outs[0]

    def predict_proba(self, X: np.ndarray,
                      n_trees: Optional[int] = None) -> np.ndarray:
        """Class probability matrix [n, K] (``multi:softprob`` semantics);
        for the binary objective, [n, 2] columns (1-p, p)."""
        p = self.param
        CHECK(p.objective in ("binary:logistic", "multi:softmax",
                              "multi:softprob"),
              f"predict_proba needs a classification objective, "
              f"got {p.objective!r}")
        margin = self.predict(X, output_margin=True, n_trees=n_trees)
        if margin.ndim == 2:           # [n, K]; the objective's is [K, n]
            return np.ascontiguousarray(
                np.asarray(self._obj.prob(jnp.asarray(margin.T))).T)
        prob1 = np.asarray(self._obj.transform(jnp.asarray(margin)))
        return np.stack([1.0 - prob1, prob1], axis=1)

    def train_margins(self) -> np.ndarray:
        """Raw training-set margins after fit (real rows only).

        ``[n]``, under a ``multi:*`` objective ``[n, K]``.
        Available after :meth:`fit` and ``fit_external(cache_device=
        True)``; the page-loop external path keeps margins per page and
        clears this state (stale-evidence rule in fit_external).  After
        a ``rank:*`` fit, margins return in the CALLER's row order (the
        handle's query order is unwound); docs truncated by
        ``max_group_size`` get NaN."""
        CHECK(getattr(self, "_train_preds", None) is not None,
              "call fit first (train_margins is unavailable after a "
              "cache_device=False external fit)")
        flat = np.asarray(self._train_preds)
        if flat.ndim == 2:             # class-major on the device
            return np.ascontiguousarray(flat[:, : self._n_real_rows].T)
        pos = getattr(self, "_rank_pos", None)
        if pos is not None:
            out = np.full(len(pos), np.nan, np.float32)
            kept = pos >= 0
            out[kept] = flat[pos[kept]]
            return out
        return flat[: self._n_real_rows]

    def _margin_shape(self, n: int) -> Tuple[int, ...]:
        """Margins ON THE DEVICE are [n] single-output and class-major
        [K, n] multiclass (``[n, K]`` only at the host's edge)."""
        K = self.param.num_class
        return (K, n) if K > 1 else (n,)

    def _margin_spec(self) -> P:
        """How device margins shard: the rows over ``data``."""
        return P(None, "data") if self.param.num_class > 1 else P("data")

    def _stacked_trees(self, trees: List[Dict[str, np.ndarray]],
                       engine: str = "incore"
                       ) -> List[Dict[str, jax.Array]]:
        """Device forest as fixed-shape chunks of ``_TREE_CHUNK`` trees
        (last chunk zero-padded at host level).

        The compiled ``_predict_trees`` program is keyed on the forest
        array's shape — stacking the EXACT tree count meant a growing
        online model recompiled the predict/margin-replay program on
        every stream refresh (jitcheck's steady-state bug class, the
        same stall shape as the PR 18 warmup miss).  A padded tree is
        all zeros, so its ``leaf[node]`` contribution is exactly 0.0 —
        margins are unchanged while every forest size ≤ the chunk
        multiple shares one compiled program per batch shape.

        A chunk's device arrays stay on the model and come back when
        the same chunk is asked for again: same position, built from
        the SAME host arrays (:meth:`_ForestChunk.holds`) —
        ``self.trees`` is appended to, cut and replaced from outside
        the class, so nothing weaker would hold.  A growing forest
        rebuilds its partial last chunk only; a prefix (``n_trees=``,
        the early-stop winner) shares the full chunks.  The model keeps
        the chunks of the forest last asked for, and one partial chunk
        more so that a prefix and the whole forest can take turns.
        Concurrent callers each build what they miss and publish with
        one assignment.  ``engine`` labels the counter."""
        keys = _forest_keys(trees)
        kept = self._forest_chunks
        chunks: List[_ForestChunk] = []
        with span("dmlc.predict.stack", trees=len(trees)) as sp:
            for index, lo in enumerate(range(0, len(trees), _TREE_CHUNK)):
                part = trees[lo:lo + _TREE_CHUNK]
                hosts = [t[k] for t in part for k in keys]
                chunks.append(
                    next((c for c in kept if c.holds(index, hosts)), None)
                    or _ForestChunk(index, hosts, _put_chunk(part, keys)))
            built = [c for c in chunks if c not in kept]
            n_hit = len(chunks) - len(built)
            sp.set(bytes=sum(v.nbytes for c in built
                             for v in c.arrays.values()),
                   chunks_hit=n_hit, chunks_built=len(built))
        spare = next((c for c in kept
                      if c.n_trees < _TREE_CHUNK and c not in chunks), None)
        keep = tuple(chunks) + (() if spare is None else (spare,))
        if keep != kept:
            self._forest_chunks = keep
        if _metrics.enabled():
            count = gbt_metrics()["forest_chunks"]
            count.inc(n_hit, engine=engine, result="hit")
            count.inc(len(built), engine=engine, result="built")
        return [c.arrays for c in chunks]

    def _apply_trees(self, bins, stacked, init):
        """Add the chunked forest's margins onto ``init`` ([n] or
        [n, K]) — one fixed-shape ``_predict_trees`` dispatch per chunk,
        margins threaded through so summation order matches the
        incremental updates that built them."""
        margin = init
        for chunk in stacked:
            margin = _add_trees(bins, chunk, margin, self.param.max_depth,
                                self._miss_bin())
        return margin

    # ------------------------------------------------------------------
    # persistence & introspection
    # ------------------------------------------------------------------
    _MODEL_MAGIC = b"DCTGBT01"

    def __getstate__(self) -> Dict[str, Any]:
        """``pickle`` and ``copy`` carry the model and never the device
        forest: :meth:`_stacked_trees` builds it again from ``trees``."""
        return {**self.__dict__, "_forest_chunks": ()}

    def save_model(self, uri: str) -> None:
        """Serialize params + bin cuts + trees to any Stream URI
        (local/S3/GCS/WebHDFS/Azure — the reference's Booster::Save over
        ``dmlc::Stream`` checkpoint layering, SURVEY.md §5)."""
        from dmlc_core_tpu.io.serializer import write_obj
        from dmlc_core_tpu.io.stream import Stream

        CHECK(self.cuts is not None and len(self.trees) > 0,
              "save_model before fit")
        s = Stream.create(uri, "w")
        try:
            s.write(self._MODEL_MAGIC)
            write_obj(s, {
                "param": self.param.to_dict(),
                "cuts": np.asarray(self.cuts),
                "trees": self.trees,
                # early-stopping state must survive the round trip or a
                # reloaded model would silently predict with the overfit
                # post-best tail
                "best_iteration": self.best_iteration,
                "best_score": self.best_score,
                "early_stopped": getattr(self, "_early_stopped", False),
                "missing": self._missing,
            })
        finally:
            s.close()

    @classmethod
    def load_model(cls, uri: str, mesh: Optional[Mesh] = None) -> "HistGBT":
        """Inverse of :meth:`save_model`; the loaded model predicts
        immediately (honoring a saved early-stop best_iteration) and
        continues training via :meth:`fit` — continued fits reuse the
        saved bin cuts and start from the ensemble's margins."""
        from dmlc_core_tpu.io.serializer import read_obj
        from dmlc_core_tpu.io.stream import Stream

        s = Stream.create(uri, "r")
        try:
            magic = s.read(len(cls._MODEL_MAGIC))
            CHECK_EQ(bytes(magic), cls._MODEL_MAGIC,
                     f"not a HistGBT model: {uri}")
            payload = read_obj(s)
        finally:
            s.close()
        model = cls(mesh=mesh)
        model.param.init(payload["param"])
        model._obj = OBJECTIVES[model.param.objective]
        model.cuts = jnp.asarray(payload["cuts"])
        model.trees = [dict(t) for t in payload["trees"]]
        model.best_iteration = payload.get("best_iteration")
        model.best_score = payload.get("best_score")
        model._early_stopped = payload.get("early_stopped", False)
        model._missing = payload.get("missing", False)
        return model

    def dump_model(self, with_stats: bool = False,
                   feature_names: Optional[List[str]] = None) -> str:
        """XGBoost-style text dump of the ensemble (``booster[i]:`` per
        tree, one node per line) — the debugging/inspection surface of
        ``Booster.dump_model``.

        A loss-guide tree prints the ids of its node list (root 0,
        expansion ``k``'s children ``2k+1`` / ``2k+2``).  A depth-wise
        tree's node ids follow the complete-binary-tree layout it has:
        node ``n`` of level ``ℓ`` is id ``2^ℓ−1+n``
        with children ``2^(ℓ+1)−1+2n`` / ``+2n+1``; the leaf
        layer sits at level ``max_depth``.  Split conditions print the
        REAL feature threshold (``cuts[f][thr]`` — bins are internal),
        as ``[f<N>≤x]`` with yes=left; a split of a categorical feature
        prints the codes of its left set, ``[f<N>:{3,17,other}]``
        (``other``: the bin of the rare and the unseen levels).
        Degenerate nodes (no profitable
        split: every row goes left) print as ``passthrough``.
        ``with_stats`` appends each real split's stored gain;
        ``feature_names`` replaces the ``f<N>`` placeholders (XGBoost's
        fmap role)."""
        CHECK(len(self.trees) > 0, "no trees trained")
        cuts = np.asarray(self.cuts)
        if feature_names is not None:
            CHECK_EQ(len(feature_names), cuts.shape[0],
                     "feature_names length must equal n_features")
        def fname(f: int) -> str:
            return feature_names[f] if feature_names is not None else f"f{f}"
        B = self.param.n_bins
        cat = self._cat_flags() or (False,) * cuts.shape[0]
        lines: List[str] = []

        def set_codes(f: int, words) -> str:
            """The codes of feature ``f``'s bins in the set ``words``."""
            bins_in = [b for b in range(B)
                       if (int(words[b >> 5]) >> (b & 31)) & 1]
            return ",".join(
                "other" if b >= cuts.shape[1] else f"{int(cuts[f][b])}"
                for b in bins_in if b >= cuts.shape[1] or cuts[f][b] >= 0)

        def dump_one(feat_t, thr_t, gain_t, leaf_t, dir_t=None,
                     cats_t=None):
            feat_t = np.asarray(feat_t)
            thr_t = np.asarray(thr_t)
            gain_t = None if gain_t is None else np.asarray(gain_t)
            dir_t = None if dir_t is None else np.asarray(dir_t)
            n_levels = feat_t.shape[0]
            for level in range(n_levels):
                n_nodes = 1 << level
                for nid in range(n_nodes):
                    gid = (1 << level) - 1 + nid
                    f = int(feat_t[level][nid])
                    t = int(thr_t[level][nid])
                    kid = (1 << (level + 1)) - 1 + 2 * nid
                    if t >= B - 1:
                        lines.append(f"\t{gid}:passthrough "
                                     f"yes={kid},no={kid + 1}")
                        continue
                    miss = ""
                    if dir_t is not None:     # XGBoost's missing= target
                        d = int(dir_t[level][nid])
                        miss = f",missing={kid if d == 1 else kid + 1}"
                    stat = ""
                    if with_stats and gain_t is not None:
                        stat = f",gain={float(gain_t[level][nid]):.6g}"
                    # missing mode's top value threshold (t == #cuts) is
                    # a missingness-only split: every finite value left
                    cond = (f"{fname(f)}:{{"
                            f"{set_codes(f, cats_t[level][nid])}}}"
                            if cat[f] else
                            f"{fname(f)}<{cuts[f][t]:.6g}"
                            if t < cuts.shape[1] else f"{fname(f)}<inf")
                    lines.append(
                        f"\t{gid}:[{cond}] "
                        f"yes={kid},no={kid + 1}{miss}{stat}")
            base = (1 << n_levels) - 1
            for i, v in enumerate(np.asarray(leaf_t)):
                lines.append(f"\t{base + i}:leaf={float(v):.6g}")

        def dump_nodes(tree):
            """A loss-guide tree: the node list's own ids, the nodes the
            root reaches in id order (unused entries are skipped)."""
            feat_t, thr_t, left_t, right_t, value_t = (
                np.asarray(tree[k]) for k in ("feat", "thr", "left",
                                              "right", "value"))
            reach, todo = set(), [0]
            while todo:
                i = todo.pop()
                reach.add(i)
                if left_t[i] > 0:
                    todo += [int(left_t[i]), int(right_t[i])]
            for i in sorted(reach):
                if left_t[i] <= 0:
                    lines.append(f"\t{i}:leaf={float(value_t[i]):.6g}")
                    continue
                f, t = int(feat_t[i]), int(thr_t[i])
                stat = (f",gain={float(tree['gain'][i]):.6g}"
                        if with_stats and "gain" in tree else "")
                lines.append(f"\t{i}:[{fname(f)}<{cuts[f][t]:.6g}] "
                             f"yes={int(left_t[i])},no={int(right_t[i])}"
                             f"{stat}")

        for ti, tree in enumerate(self.trees):
            feat_t = np.asarray(tree["feat"])
            if "left" in tree:              # node list [(K,) M]
                if feat_t.ndim == 2:
                    for c in range(feat_t.shape[0]):
                        lines.append(f"booster[{ti}] class[{c}]:")
                        dump_nodes({k: v[c] for k, v in tree.items()})
                else:
                    lines.append(f"booster[{ti}]:")
                    dump_nodes(tree)
            elif feat_t.ndim == 3:          # multiclass [K, depth, half]
                for c in range(feat_t.shape[0]):
                    lines.append(f"booster[{ti}] class[{c}]:")
                    dump_one(tree["feat"][c], tree["thr"][c],
                             tree["gain"][c] if "gain" in tree else None,
                             tree["leaf"][c],
                             tree["dir"][c] if "dir" in tree else None,
                             tree["cats"][c] if "cats" in tree else None)
            else:
                lines.append(f"booster[{ti}]:")
                dump_one(tree["feat"], tree["thr"], tree.get("gain"),
                         tree["leaf"], tree.get("dir"), tree.get("cats"))
        return "\n".join(lines) + "\n"

    def feature_importances(self, importance_type: str = "weight"
                            ) -> np.ndarray:
        """Per-feature importance over the ensemble.

        ``"weight"``: number of real (non-degenerate, non-padding) splits
        using each feature; ``"gain"``: total split gain accumulated per
        feature (XGBoost's default notion of importance).  Degenerate/
        early-stopped nodes are written with ``thr == n_bins-1`` and
        level padding with ``thr == 0`` past the level's node count, so
        only genuine splits are counted.
        """
        CHECK(len(self.trees) > 0, "no trees trained")
        if importance_type not in ("weight", "gain"):
            log_fatal(f"unsupported importance_type {importance_type!r}")
        if importance_type == "gain":
            CHECK(all("gain" in t for t in self.trees),
                  "importance_type='gain' needs trees with stored gains "
                  "(models saved before gain tracking have none)")
        F = int(np.asarray(self.cuts).shape[0])
        out = np.zeros(F, np.float64 if importance_type == "gain"
                       else np.int64)
        B = self.param.n_bins
        for tree in self.trees:
            feat_t = np.asarray(tree["feat"])
            thr_t = np.asarray(tree["thr"])
            gain_t = (np.asarray(tree["gain"])
                      if importance_type == "gain" else None)
            if "left" in tree:              # node list: the split nodes
                split = np.asarray(tree["left"]) > 0
                np.add.at(out, feat_t[split],
                          gain_t[split] if gain_t is not None else 1)
                continue
            if feat_t.ndim == 2:            # single-output: [depth, half]
                feat_t, thr_t = feat_t[None], thr_t[None]
                gain_t = None if gain_t is None else gain_t[None]
            for c, (feat_c, thr_c) in enumerate(zip(feat_t, thr_t)):
                for level in range(feat_c.shape[0]):
                    n_nodes = 1 << level
                    feat = feat_c[level][:n_nodes]
                    thr = thr_c[level][:n_nodes]
                    real = thr < B - 1      # degenerate splits use B-1
                    if importance_type == "gain":
                        np.add.at(out, feat[real],
                                  gain_t[c][level][:n_nodes][real])
                    else:
                        np.add.at(out, feat[real], 1)
        return out


#: trees per compiled predict/margin-replay program (``_stacked_trees``
#: pads forests to a multiple of this) — the program's shape must not
#: track ensemble size, or every online refresh recompiles it
_TREE_CHUNK = 64


class _ForestChunk:
    """One chunk of ``_stacked_trees`` as the model keeps it: the device
    arrays, and the host arrays they were built from.  The references
    are held, so the identity of a host array cannot come back as
    another array's while the chunk lives.  Compared by identity."""

    __slots__ = ("index", "n_trees", "hosts", "arrays")

    def __init__(self, index: int, hosts: List[np.ndarray],
                 arrays: Dict[str, jax.Array]) -> None:
        self.index = index
        self.n_trees = len(hosts) // len(arrays)
        self.hosts = hosts
        self.arrays = arrays

    def holds(self, index: int, hosts: List[np.ndarray]) -> bool:
        """True if this is chunk ``index`` of a forest whose tables there
        are ``hosts``, array for array the same objects."""
        return (index == self.index and len(hosts) == len(self.hosts)
                and all(map(operator.is_, hosts, self.hosts)))


def _put_chunk(part: List[Dict[str, np.ndarray]], keys: Tuple[str, ...]
               ) -> Dict[str, jax.Array]:
    """Stack the tables of at most ``_TREE_CHUNK`` trees, zero-pad to
    the chunk and put each on the device."""
    pad = _TREE_CHUNK - len(part)
    arrays = {}
    for k in keys:
        v = np.stack([t[k] for t in part])
        if pad:
            v = np.concatenate([v, np.zeros((pad,) + v.shape[1:], v.dtype)])
        arrays[k] = jnp.asarray(v)
    return arrays


def _forest_keys(trees: List[Dict[str, np.ndarray]]) -> Tuple[str, ...]:
    """The tables a device forest of ``trees`` carries: depth-wise trees'
    level arrays, or loss-guide trees' node lists.  One forest, one
    form."""
    node_lists = "left" in trees[0]
    CHECK(all(("left" in t) == node_lists for t in trees),
          "the ensemble mixes depth-wise trees and loss-guide node lists "
          "(a fit continued under another grow_policy): they cannot be "
          "scored as one forest")
    if node_lists:
        return ("feat", "thr", "left", "right", "value")
    CHECK(all(("cats" in t) == ("cats" in trees[0]) for t in trees),
          "the ensemble mixes trees with and without categorical sets (a "
          "fit continued under other feature_types)")
    return (("feat", "thr", "leaf") + (("dir",) if "dir" in trees[0] else ())
            + (("cats",) if "cats" in trees[0] else ()))


def _walk_node_list(bins, tree):
    """Each row's leaf in ONE node list (``left`` > 0 marks a split
    node: the root is nobody's child, so an all-zero padding tree stays
    at node 0, whose value is 0), by following children until no row
    moves — a tree of any depth, one program.  Plain gathers: no cell
    times this walker yet (PERF.md section 7)."""
    feat, thr, left, right = (tree[k] for k in ("feat", "thr", "left",
                                                "right"))

    def step(node):
        row_bin = jnp.take_along_axis(
            bins, feat[node][:, None], axis=1)[:, 0].astype(jnp.int32)
        to_left = left[node]
        nxt = jnp.where(row_bin > thr[node], right[node], to_left)
        return jnp.where(to_left > 0, nxt, node)

    return jax.lax.while_loop(lambda node: jnp.any(left[node] > 0), step,
                              jnp.zeros(bins.shape[0], jnp.int32))


@jax.jit
@jax.named_scope("dmlc.descend.nodes")
def _add_node_lists(bins, forest, margin):
    """``margin`` ([n], multiclass class-major [K, n]) plus the leaf
    values of a forest of node lists [T, (K,) M], tree after tree in
    float32: the summation order of the updates that built the
    margins."""
    def one_class(trees, margin):
        def one_tree(margin, tree):
            return margin + tree["value"][_walk_node_list(bins, tree)], None

        return jax.lax.scan(one_tree, margin, trees)[0]

    if forest["feat"].ndim == 3:       # multiclass: [T, K, M]
        by_class = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0), forest)
        return jax.lax.map(lambda a: one_class(*a), (by_class, margin))
    return one_class(forest, margin)


@jax.jit
@jax.named_scope("dmlc.descend.nodes")
def _node_list_leaves(bins, forest):
    """Per-tree leaf node ids [n, T] (multiclass [n, T, K]) of a forest
    of node lists (predict_leaf)."""
    walk = partial(_walk_node_list, bins)
    if forest["feat"].ndim == 3:
        walk = jax.vmap(walk)
    return jnp.moveaxis(jax.lax.map(walk, forest), -1, 0)


#: row-trees one block of the descent walks at once: each of a block's
#: [trees, rows] intermediates is 512 KiB of int32, whatever n and T
#: are.  Swept on a v5e (PERF.md §6, PR 40): at 2^20 the same trees
#: cost 1.7-2.4x as much, at every width and depth tried
_DESCEND_BLOCK = 1 << 17

#: trees a block walks: the fewest and the most.  A block reads its
#: rows' bins once a level for all its trees, so it takes more trees
#: the wider the rows are (:func:`_descend_blocks`)
_TREE_BLOCK = (8, 32)

#: a block's rows are laid out [rows/128, 128] behind the tree axis —
#: whole (8, 128) tiles, so a tree's table entry is a scalar on the VPU
_ROW_TILE = 8 * 128

#: longest axis :func:`_select` sums at once; a longer one is folded in
#: two (why: ``_CUT_FOLD`` in ops/quantile.py — XLA:CPU cannot fuse the
#: compare into a longer reduce, the TPU compiler does not care)
_SELECT_FOLD = 32


def _select(entries, idx):
    """``entries[:, idx]`` along axis 1 with no gather: compare ``idx``
    [trees, rows/128, 128] with every position and sum the one entry
    that matches (``table_select`` of the round program, vectorised over
    trees).  ``entries`` broadcasts against ``idx`` on its other axes:
    [trees, N, 1, 1] is a table per tree, [1, F, rows/128, 128] a value
    per row.  A TPU does a gather one element at a time (12.8 ns a node
    visit on a v5e: PERF.md §6, PR 30); this is one loop fusion.  Exact:
    one term of the sum is not zero."""
    n, tail = entries.shape[1], entries.shape[2:]
    groups = -(-n // _SELECT_FOLD)
    width = -(-n // groups)
    # positions past n match no index
    entries = jnp.pad(entries, ((0, 0), (0, groups * width - n))
                      + ((0, 0),) * len(tail))
    entries = entries.reshape((-1, groups, width) + tail)
    pos = jnp.arange(groups * width, dtype=jnp.int32).reshape(
        (1, groups, width) + (1,) * len(tail))
    return jnp.sum(jnp.where(idx[:, None, None] == pos, entries, 0),
                   axis=(1, 2))


def _descend(bins_t, feats, thrs, dirs, depth: int, miss_bin: int,
             cats=None):
    """Leaf position [trees, rows/128, 128] of a block's rows in a
    block's trees, level by level and gather-free: at each level select
    the row's node's feature, then the row's bin of that feature, and
    route right on bin > thr; missing rows (bin == miss_bin; only
    produced in missing mode) follow the node's learned direction
    (1 = left).  ``bins_t`` is [F, rows/128, 128] int32, the tables
    [trees, depth, half].  ``cats`` [trees, depth, half, W] (a model with
    categorical columns) holds EVERY node's left set as bit words — a
    numeric split's is its bins <= thr — and a row goes left iff its bin
    is in its node's: word ``bin // 32`` of the node, selected among the
    level's ``W x nodes``, then the bit."""
    node = jnp.zeros(feats.shape[:1] + bins_t.shape[1:], jnp.int32)
    for level in range(depth):
        def of_node(table):
            # (indexed in two steps: one mixed index lowers to a gather)
            return _select(table[:, level, :1 << level][:, :, None, None],
                           node)
        row_bin = _select(bins_t[None], of_node(feats))
        if cats is not None:
            n_words = cats.shape[-1]
            words = cats[:, level, :1 << level].reshape(cats.shape[0], -1)
            word = _select(words[:, :, None, None],
                           node * n_words + (row_bin >> 5))
            go_right = ((word >> (row_bin & 31)) & 1) == 0
        else:
            go_right = row_bin > of_node(thrs)
        if dirs is not None:
            go_right = jnp.where(row_bin == miss_bin, of_node(dirs) == 0,
                                 go_right)
        node = 2 * node + go_right.astype(jnp.int32)
    return node


def _descend_blocks(n: int, n_trees: int, n_features: int
                    ) -> Tuple[int, int, int]:
    """(row blocks, rows a block, tree blocks) for n rows × n_trees:
    8 trees a block and one more for every 64 features, at most 32, and
    ``_DESCEND_BLOCK`` row-trees a block, rows in whole tiles — static
    functions of the shapes, so memory is bounded whatever n is."""
    fewest, most = _TREE_BLOCK
    tree_blocks = -(-n_trees // min(max(n_features // 64, fewest), most))
    trees = -(-n_trees // tree_blocks)
    rows = max(_DESCEND_BLOCK // trees // _ROW_TILE, 1) * _ROW_TILE
    rows = min(rows, -(-n // _ROW_TILE) * _ROW_TILE)
    return -(-n // rows), rows, tree_blocks


def _row_blocks(a, row_blocks: int, rows: int):
    """``a`` [n, ...] → [row_blocks, ..., rows/128, 128], zero-padded.
    Row i sits in block ``i % row_blocks``: strided, so that a sharding
    of n over a mesh stays a sharding of every block's rows (contiguous
    blocks would put each block on one device)."""
    a = jnp.pad(a, ((0, row_blocks * rows - a.shape[0]),)
                + ((0, 0),) * (a.ndim - 1))
    a = jnp.moveaxis(a.reshape((rows, row_blocks) + a.shape[1:]), 0, -1)
    return a.reshape(a.shape[:-1] + (rows // 128, 128))


def _rows_of_blocks(a, n: int):
    """Inverse of :func:`_row_blocks`: [row_blocks, ..., rows/128, 128]
    → [n, ...]."""
    a = jnp.moveaxis(a.reshape(a.shape[:-2] + (-1,)), -1, 0)
    return a.reshape((-1,) + a.shape[2:])[:n]


def _tree_blocks(a, tree_blocks: int):
    """``a`` [T, ...] → [tree_blocks, T/tree_blocks, ...], padded with
    all-zero trees (they select leaf 0 = 0.0, as ``_stacked_trees``'s)."""
    trees = -(-a.shape[0] // tree_blocks)
    a = jnp.pad(a, ((0, tree_blocks * trees - a.shape[0]),)
                + ((0, 0),) * (a.ndim - 1))
    return a.reshape((tree_blocks, trees) + a.shape[1:])


@partial(jax.jit, static_argnums=(4, 8))
@jax.named_scope("dmlc.descend")
def _predict_trees(bins, feats, thrs, leaves, depth: int,
                   base_score: float = 0.0, init=None,
                   dirs=None, miss_bin: int = -1, cats=None):
    """Sum leaf values over trees: a dense, gather-free descent
    (:func:`_descend`) over blocks of rows × blocks of trees.

    ``init`` carries margins from already-applied trees (the incremental
    validation path); otherwise margins start at ``base_score``.
    ``dirs``/``miss_bin`` enable missing-mode routing, ``cats`` (every
    node's left set) routing by membership.  A row's answer
    depends on neither the block sizes nor the other rows of the call.
    """
    n = bins.shape[0]
    if init is None:
        init = jnp.full(n, base_score, jnp.float32)
    row_blocks, rows, tree_blocks = _descend_blocks(n, feats.shape[0],
                                                   bins.shape[1])
    # (a tree map skips dirs=None and cats=None)
    trees = jax.tree.map(partial(_tree_blocks, tree_blocks=tree_blocks),
                         (feats, thrs, dirs, leaves, cats))

    def row_block(block):
        bins_t, margin = block
        bins_t = bins_t.astype(jnp.int32)

        def tree_block(margin, tree):
            feat, thr, dirv, leaf, sets = tree
            node = _descend(bins_t, feat, thr, dirv, depth, miss_bin, sets)
            vals = _select(leaf[:, :, None, None], node)
            # in tree order, in float32: the summation order of the
            # incremental updates that built the margins
            for t in range(vals.shape[0]):
                margin = margin + vals[t]
            return margin, None

        return jax.lax.scan(tree_block, margin, trees)[0]

    return _rows_of_blocks(
        jax.lax.map(row_block, (_row_blocks(bins, row_blocks, rows),
                                _row_blocks(init, row_blocks, rows))), n)


def _add_trees(bins, forest, margin, depth: int, miss_bin: int):
    """``margin`` ([n], multiclass class-major [K, n]) plus the leaf
    values of ``forest``, a dict of tree tables [T, ...] (multiclass
    [T, K, ...]: class c's trees add onto row c) —
    :func:`_predict_trees` with the margin as its ``init``, for several
    classes ONE descent program walked K times (``lax.map`` over the
    class axis)."""
    if "left" in forest:               # loss-guide trees: node lists
        return _add_node_lists(bins, forest, margin)
    dirs, cats = forest.get("dir"), forest.get("cats")
    if forest["feat"].ndim == 4:       # multiclass: [T, K, depth, half]
        by_class = jax.tree.map(
            lambda a: jnp.moveaxis(a, 1, 0),
            (forest["feat"], forest["thr"], forest["leaf"], dirs, cats))

        def one_class(args):
            (feat, thr, leaf, dirv, sets), init = args
            return _predict_trees(bins, feat, thr, leaf, depth, 0.0, init,
                                  dirv, miss_bin, sets)

        return jax.lax.map(one_class, (by_class, margin))
    return _predict_trees(bins, forest["feat"], forest["thr"],
                          forest["leaf"], depth, 0.0, margin, dirs, miss_bin,
                          cats)


@partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _predict_slab(x, cuts, chunks, depth: int, miss_bin: int,
                  base_score: float, transform, cat=None):
    """One slab of ``predict`` as ONE program: bin ``x`` [n, F] (float32,
    on the device) against ``cuts``, start the margins at ``base_score``
    (a constant of the program), add the forest's leaf values and apply
    ``transform`` (the objective's, a static method; None = margins).

    ``chunks`` is what ``_stacked_trees`` returns.  Laid end to end they
    are ONE forest of ``len(chunks) * _TREE_CHUNK`` trees, which
    :func:`_predict_trees` walks as it walks any: a ``lax.scan`` over
    blocks of trees, the margins threaded through in tree order —
    every float32 sum of one ``_predict_trees`` call a chunk, in the
    same order.  The program is keyed on the slab's
    shape, the NUMBER of chunks, whether they carry ``dir``, and the
    statics: a forest that grows inside a chunk, or a prefix with as
    many chunks, compiles nothing; one that crosses a ``_TREE_CHUNK``
    mark compiles once per slab shape.  ``cat``: the categorical
    columns, binned by their tables (None: no such column)."""
    if cat is not None:
        bins = apply_bins(x, cuts, cat=cat)
    elif miss_bin < 0:
        bins = apply_bins(x, cuts)
    else:
        bins = apply_bins_missing(x, cuts, miss_bin)
    with jax.named_scope("dmlc.descend"):
        forest = {k: jnp.concatenate([chunk[k] for chunk in chunks])
                  for k in chunks[0]}
    # () or, multiclass, (K,): [T, (K,) n_leaf], node lists [T, (K,) M]
    n_out = forest["value" if "left" in forest else "leaf"].shape[1:-1]
    margin = _add_trees(
        bins, forest,
        jnp.full(n_out + x.shape[:1], base_score, jnp.float32),
        depth, miss_bin)
    return margin if transform is None else transform(margin)


@partial(jax.jit, static_argnums=(3, 5))
@jax.named_scope("dmlc.descend")
def _leaf_indices(bins, feats, thrs, depth: int, dirs=None,
                  miss_bin: int = -1, cats=None):
    """Per-tree leaf assignment [n, T] (predict_leaf); the same
    :func:`_descend` as _predict_trees, collecting the final node instead
    of summing leaf values."""
    n, n_trees = bins.shape[0], feats.shape[0]
    row_blocks, rows, tree_blocks = _descend_blocks(n, n_trees,
                                                   bins.shape[1])
    trees = jax.tree.map(partial(_tree_blocks, tree_blocks=tree_blocks),
                         (feats, thrs, dirs, cats))

    def row_block(bins_t):
        bins_t = bins_t.astype(jnp.int32)
        nodes = jax.lax.map(
            lambda tree: _descend(bins_t, *tree[:3], depth, miss_bin,
                                  tree[3]), trees)
        return nodes.reshape((-1,) + nodes.shape[2:])[:n_trees]

    return _rows_of_blocks(
        jax.lax.map(row_block, _row_blocks(bins, row_blocks, rows)), n)
