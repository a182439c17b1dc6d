"""Gradient histograms for hist-method gradient boosting.

The hot op of XGBoost-style training (BASELINE config 1): for every tree
node, feature and bin, accumulate Σgrad and Σhess of the rows that land
there.  Two engines, selected by ``method``:

* ``"segment"`` — one flat ``segment_sum`` over the combined
  ``(node, feature, bin)`` index, run separately for grad and hess.
  Lowers to XLA scatter-add: fast on CPU, slow on TPU (scatter
  serializes); the CPU default.
* ``"pallas"`` — the factored one-hot Pallas kernel (``_hist_pallas``),
  at any dense feature count: where the whole matrix is more than the
  kernel's VMEM budgets admit, the build runs in FEATURE BLOCKS
  (:func:`hist_feature_blocks`), the same kernel once per block of rows
  of the feature-major matrix, joined on the feature axis; and at any
  node count: where a build has more nodes than the budgets admit in
  one call (more than 32 at 256 bins: the last level of a depth-8
  tree), it runs in NODE BLOCKS (:func:`hist_node_blocks`), the same
  kernel once per block of nodes over all rows, joined on the node
  axis; and with a CLASS axis: ``node_id``, ``grad``, ``hess`` given
  class-major ``[K, n]`` (a multiclass round: K trees grown level by
  level together) build ``[K, 2, n_nodes, F, n_bins]``, one kernel call
  per block of classes (:func:`hist_class_blocks`) whose one-hots are
  stacked to the MXU's 128 rows over ONE read of the bins block and
  ONE right operand a feature.
* ``"auto"`` — :func:`resolve_hist_method`: on TPU ``pallas`` wherever
  the kernel's VMEM budgets admit a feature block of even ONE node
  (every plain dense matrix, at any depth); ``segment`` for a packed
  layout whose rows, which cannot be cut, do not fit, and on every
  other backend.  An EXPLICIT method is never rewritten: asking for
  ``pallas`` at a shape the budgets refuse raises.

TPU layout note: the result is ``[2, n_nodes, F, n_bins]`` with the
grad/hess plane LEADING.  A trailing axis of size 2 is catastrophic under
the TPU ``T(8,128)`` tiled layout — the minor dimension pads 2 → 128, a
64× memory blowup (observed as a 57GB alloc for a ``f32[112e6, 2]`` on a
16GB chip).  Never stack grad/hess on the minor axis of a large array.

All formulations are pure functions of arrays — safe inside
jit/shard_map; the data-parallel trainer psums the result over the mesh's
``data`` axis (the histogram-sync allreduce that replaces rabit's socket
tree, SURVEY.md §5; reference: rabit's Allreduce over
``tracker/dmlc_tracker/tracker.py :: get_tree`` topology).
"""

from __future__ import annotations

from functools import partial
from itertools import accumulate, pairwise

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dmlc_core_tpu.base.logging import CHECK, log_fatal
from dmlc_core_tpu.ops import binlayout as _bl

__all__ = ["build_histogram", "descend_histogram",
           "select_feature_bins", "histogram_methods",
           "resolve_hist_method", "pallas_interpret",
           "reference_histogram", "hist_psum_bytes_per_round",
           "bins_bytes_per_round", "leaves_built_per_round",
           "hist_feature_dots", "hist_feature_blocks",
           "hist_node_blocks", "hist_class_blocks",
           "RECLUSTER_MIN_ROWS", "recluster_points", "recluster_rows",
           "hist_tile_rows", "tile_aligned", "tile_liveness"]


def leaves_built_per_round(depth: int, grow_policy: str = "depthwise",
                           max_leaves: int = 0) -> int:
    """Histogram BUILDS one boosting round pays (sibling subtraction
    derives the rest for free).  Depth-wise: the root plus every level's
    left children — ``2^(depth-1)``.  Loss-guide builds only for the
    expanded leaf: the root plus one per expansion — the tree's leaves:
    ``max_leaves`` and ``2^depth``, whichever is set (not 0) and smaller.
    Feeds bench.py's ``kernel.leaves_built_per_round`` regression
    field."""
    if grow_policy == "lossguide":
        return min(v for v in (max_leaves, (1 << depth) if depth else 0)
                   if v)
    return 1 if depth <= 1 else 1 << (depth - 1)


def hist_feature_dots(n_features: int, layout=None) -> tuple[int, int]:
    """``(emitted, padded)``: the MXU dots the Pallas kernels issue per
    row tile — one per REAL row of the feature-major bin matrix
    (``n_features`` rows, or a ``layout``'s physical rows, two dots per
    nibble-packed row) — and the dots the 8-row-padded block they read
    would be if its zero rows counted.  HIGGS: ``(28, 32)``.  A record
    (``HistGBT.round_plan``'s ``hist_features``) and the tests' oracle;
    it selects nothing."""
    if layout is None:
        return n_features, -(-n_features // 8) * 8
    rows, packed = layout.phys_rows, layout.packed_rows
    return rows + packed, -(-rows // 8) * 8 + packed


def hist_psum_bytes_per_round(depth: int, n_features: int,
                              n_bins: int, *, layout=None,
                              grow_policy: str = "depthwise",
                              max_leaves: int = 0) -> int:
    """Per-chip bytes contributed to the in-step histogram-sync
    allreduce by ONE boosting round (one tree).

    Per level ℓ of the sibling-subtracted depth-wise engine only the
    built histograms cross the wire: the root at level 0, then LEFT
    children only (``n_build = 2^(ℓ-1)``).  The loss-guide engine syncs
    one built node per expansion (root + ``max_leaves − 1``).  Each
    built node is ``[2, S, Bs]`` f32 (grad + hess planes) where a
    non-trivial :class:`~dmlc_core_tpu.ops.binlayout.BinLayout` shrinks
    S below F (bundling) and Bs below B (histograms build and sync at
    the widest USED storage width, then zero-pad back before split
    evaluation).  This is the single analytic model behind bench.py's
    ``hist_psum_bytes_per_round`` field and the live
    ``dmlc_histogram_psum_bytes_total`` counter — the cross-chip
    traffic the multi-chip flagship pays per round (the rabit-allreduce
    replacement's byte bill).
    """
    if layout is not None:
        n_features = layout.storage_features
        n_bins = layout.sync_bins
    node_bytes = 2 * n_features * n_bins * 4
    if grow_policy == "lossguide":
        return leaves_built_per_round(depth, "lossguide",
                                      max_leaves) * node_bytes
    total = 0
    for level in range(depth):
        n_build = 1 if level == 0 else 1 << (level - 1)
        total += n_build * node_bytes
    return total


def bins_bytes_per_round(depth: int, rows: int, row_bytes: int, *,
                         grow_policy: str = "depthwise",
                         max_leaves: int = 0) -> int:
    """Bin-matrix HBM bytes ONE boosting round streams: the number of
    full passes over the ``[phys_rows, n]`` matrix times its size.

    Depth-wise: level 0 a histogram pass, every deeper level a descend
    pass plus a histogram pass, the final leaf assignment one more
    descend — ``2·depth − 1`` passes.  Loss-guide: ``2·leaves − 1``.
    Not counted: a level built in k NODE blocks
    (:func:`hist_node_blocks`; ``max_depth`` >= 8 at 256 bins) reads
    the matrix k times for its histogram, not once.
    Feeds bench.py's ``kernel.bins_bytes_per_round`` field and the HBM
    roofline estimate.
    """
    if grow_policy == "lossguide":
        leaves = leaves_built_per_round(depth, "lossguide", max_leaves)
        passes = 2 * leaves - 1
    else:
        passes = 2 * depth - 1
    return max(passes, 1) * rows * row_bytes


def histogram_methods() -> list[str]:
    """Names of the available histogram engines (``auto`` resolves per
    platform: Pallas on TPU, segment elsewhere)."""
    return ["auto", "segment", "pallas"]


#: pallas row-tile.  v5e sweeps: 8192 beat 4096 by 3-8% (round 2, 4M
#: rows); 16384 beats 8192 at the north-star 10M shape at most levels
#: (L0/L2/L3/L5 by 5-25%, L1/L4 within noise) — scripts/sweep_hist.py.
_TILE_ROWS = 16384


#: scoped VMEM a TPU kernel may hold (the compiler's limit on v5e), and
#: what of it the histogram kernel needs beside its double-buffered bins
#: block, per row of the tile.  Read off the v5e compiler at tile 16384,
#: 256 bins (PERF.md section 6, PR 35): a build's scoped allocation is
#: ``2·T·Fp`` bytes + 2.91-3.62 MiB for n_nodes 1..16; 240 B a row keeps
#: 3.75 MiB.
_SCOPED_VMEM = 16 << 20
_SCOPED_ROW_RESERVE = 240
#: the scoped-VMEM limit every call of a build cut on FEATURES states
#: for itself (a matrix wider than one feature block, whether or not
#: node blocks lie around the feature blocks).  Alone, and in some
#: programs, each of those calls fits the compiler's 16 MiB; in other
#: programs around the very same calls the v5e compiler charges them
#: 16.50-19.12 MiB and refuses the program, by no rule of the block's
#: size a call can observe — 17.34 MiB at 152 rows, 16.50 at 168, 19.12
#: at 200 (968 features, 32-node blocks: PERF.md section 6, PR 42);
#: 18.75 for the 8 builds of a level 4 on a 392-row block at 4,227
#: features (PR 51; compile-only client both).  Stating the limit costs
#: nothing: the same kernel, the same time (A/B on the chip, PERF.md
#: section 6, PR 51).  A v5e core has 128 MiB of VMEM.
_NESTED_BLOCKS_VMEM = 32 << 20
#: the scoped-VMEM limit a STACKED call states for itself (a block of
#: classes in one kernel, :func:`hist_class_blocks`), and what each
#: stacked class adds per row of the tile: its node, gradient and
#: hessian rows, ``[1, T]`` values that fill whole sublane tiles, and
#: its ``[8, T]`` one-hot targets of a feature group.  Read off the v5e
#: compiler at tile 16384, 56 rows, 256 bins (compile-only client,
#: PERF.md section 6, PR 49): 4.9 / 7.24 / 10.37 / 12.43 / 19.85 MiB at
#: 1 / 2 / 3 / 4 / 7 classes, whatever the level — 2.1-3.1 MiB a class;
#: 176 B a row keeps 2.75.
_STACKED_VMEM = 48 << 20
_STACKED_ROW_RESERVE = 176


#: rows of the MXU (v5e: four 128 x 128 arrays): the height a stack of
#: classes fills.  One call of A = 256 is 19% slower than two of 128
#: (PERF.md section 7 (6)), so a call never stacks past it.
_MXU_ROWS = 128


def _pallas_ok(n_bins: int, n_features: int, n_nodes: int = 1,
               bins_itemsize: int = 1, tile_rows: int = 0,
               n_class: int = 1) -> int:
    """The FEATURE BLOCK of a Pallas build at this shape: how many rows
    of the feature-major bin matrix one kernel call takes.
    ``n_features`` itself where the whole matrix fits the kernel's VMEM
    budgets (one call); else the largest multiple of 8 that fits, and the build runs block
    by block (:func:`hist_feature_blocks`); 0 where not even 8 rows fit
    in ONE call of ``n_nodes`` (``n_nodes·hi`` too tall: deep levels,
    huge bin counts).  Truthy = one kernel call serves ``n_nodes``.  A 0
    at ``n_nodes`` > 1 does not refuse the build: it runs in NODE
    BLOCKS (:func:`hist_node_blocks`), and this function answers for
    each block's node count; the kernel cannot serve a shape only where
    the answer is 0 at even ONE node (huge bin counts).

    The factored kernel works for any n_bins; the budgets, all linear
    in the block's padded rows ``Fp``, are (a) the [Fp, A, lo] f32
    accumulator block — empirically calibrated on v5e at
    tile_rows=4096: nominal accumulators up to 32MB compile and run
    (Mosaic windows the out block; fori_loop temporaries are reused, so
    per-row working-set formulas wildly overestimate), 64MB fails, 24MB
    keeps margin — (b) the tile-scaled VMEM stack: per row-tile of T
    rows the kernel holds the [Fp, T] bins block, the int32 prep ([8,T]
    blk/t0s/los), the per-feature one-hots (oh [nh,T] + lhs [2nh,T]
    bf16, rhs [lo,T] bf16) and ~6 [1,T] i32/f32 vectors —
    ≈ T·(Fp·itemsize + 120 + 6·nh + 2·lo) bytes.  Calibration anchor:
    tile 65536 at lo=32, nh=8, Fp=32 predicts 17.3MB and measurably
    OOMs the 16MB scoped-vmem limit (sweep_hist, 10M rows); tile 16384
    at the deepest default level predicts 9.8MB and runs.  The 15MB
    budget keeps margin under the measured 16MB wall — and (c) the
    pipeline's DOUBLE-buffered bins block, which (b) counts once and
    which is all that matters once Fp is in the hundreds:
    ``T·(2·Fp·itemsize + 240) <= 16 MiB`` (``_SCOPED_ROW_RESERVE``; at
    the default tile Fp <= 392, where the compiler takes 416 / 392 / 408
    rows at n_nodes 1 / 8 / 16 and refuses 424 / 400 / 416).  (a) and
    (b) alone admit a root build of 728 rows, which Mosaic refuses.
    All three stay on ``Fp``, not on the real feature count: the kernel
    skips a pad feature's dot, but its rows are still in the bins block
    it reads and in the accumulator it holds.

    ``n_class`` > 1 asks for a call that STACKS that many classes
    (:func:`hist_class_blocks`): the left operand and the out block are
    ``n_class`` times as tall at the stack's ``lo``
    (:func:`_lo_stacked`), which is what (a) and (b) have to learn;
    the call states ``_STACKED_VMEM`` for itself, so (b) and (c) count
    against that wall, (c) with ``_STACKED_ROW_RESERVE`` more a class
    (it is the term that binds a stacked call);
    0 where no ``lo`` keeps the classes' one-hots on sublane tiles."""
    lo = (_lo_factor(n_nodes, n_bins) if n_class == 1
          else _lo_stacked(n_nodes, n_bins))
    if not lo:
        return 0
    hi = -(-n_bins // lo)
    nh = n_class * n_nodes * hi
    T = tile_rows or _TILE_ROWS
    stack, wall, reserve = 15 << 20, _SCOPED_VMEM, _SCOPED_ROW_RESERVE
    if n_class > 1:
        stack = wall = _STACKED_VMEM
        reserve += n_class * _STACKED_ROW_RESERVE
    fp_max = min(
        (24 << 20) // (2 * nh * max(lo, 128) * 4),
        (stack // T - (120 + 6 * nh + 2 * lo)) // bins_itemsize,
        (wall // T - reserve) // (2 * bins_itemsize))
    if -(-n_features // 8) * 8 <= fp_max:
        return n_features
    return max(fp_max // 8 * 8, 0)


def hist_feature_blocks(n_bins: int, n_features: int, n_nodes: int = 1,
                        bins_itemsize: int = 1,
                        n_class: int = 1) -> tuple[int, ...]:
    """Rows of each feature block a Pallas build of this shape runs in,
    in matrix order: ``(n_features,)`` where one kernel call takes the
    whole matrix, else whole blocks of :func:`_pallas_ok` rows and the
    rest; ``()`` where the kernel cannot serve the shape.  What
    :func:`build_histogram` traces and ``HistGBT.round_plan`` records
    (``hist_feature_blocks``).  Epsilon's 2000 features at 256 bins:
    five blocks of 392 and one of 40, at every level of a depth-6
    tree.  ``n_class`` > 1: the blocks of a call that stacks that many
    classes."""
    fb = _pallas_ok(n_bins, n_features, n_nodes, bins_itemsize,
                    n_class=n_class)
    if not fb:
        return ()
    full, rest = divmod(n_features, fb)
    return (fb,) * full + ((rest,) if rest else ())


def _node_block(n_bins: int, n_rows: int, n_nodes: int = 1,
                bins_itemsize: int = 1, whole: bool = False) -> int:
    """The NODE BLOCK of a Pallas build at this shape: how many nodes
    one kernel call takes.  ``n_nodes`` itself wherever :func:`_pallas_ok`
    admits the build in one call (every level of a depth-wise tree up to
    depth 7 at 256 bins); else the largest power of two below it that
    the gate admits — from the gate's own budgets, from shapes alone;
    0 where not even one node is admitted.  Admitted means a feature
    block of at least 8 rows, or with ``whole`` (a nibble-packed layout,
    which cannot be cut on features) all of ``n_rows``."""
    def admits(n):
        block = _pallas_ok(n_bins, n_rows, n, bins_itemsize)
        return block >= n_rows if whole else block > 0

    if admits(n_nodes):
        return n_nodes
    nb = 1 << ((n_nodes - 1).bit_length() - 1) if n_nodes > 1 else 0
    while nb and not admits(nb):
        nb >>= 1
    return nb


def hist_node_blocks(n_bins: int, n_rows: int, n_nodes: int = 1,
                     bins_itemsize: int = 1,
                     whole: bool = False) -> tuple[int, ...]:
    """Nodes of each node block a Pallas build of this shape runs in, in
    node order: ``(n_nodes,)`` where one kernel call takes the build,
    else whole blocks of :func:`_node_block` nodes and the rest; ``()``
    where the kernel cannot serve the shape.  What
    :func:`build_histogram` traces and ``HistGBT.round_plan`` records
    (``hist_node_blocks``).  The gate by nodes at 256 bins — feature
    rows ONE call takes, ``_pallas_ok(256, F, n)``::

        n_nodes    1    2    4    8   16   32   64  128  256
        F = 28    28   28   28   28   28   28    0    0    0
        F = 2000 392  392  392  392  392  200    0    0    0

    so: one block up to 32 nodes, ``(32, 32)`` for the 64 left children
    of a depth-8 tree's last level, ``(32,) * 8`` at depth 10.  Each
    block is then built in the feature blocks of ITS node count
    (:func:`hist_feature_blocks`): 2000 features at 64 nodes are two
    node blocks of ten 200-row feature blocks."""
    nb = _node_block(n_bins, n_rows, n_nodes, bins_itemsize, whole)
    if not nb:
        return ()
    full, rest = divmod(n_nodes, nb)
    return (nb,) * full + ((rest,) if rest else ())


def _class_block(n_bins: int, n_rows: int, n_nodes: int, n_class: int,
                 bins_itemsize: int = 1, whole: bool = False) -> int:
    """The CLASS BLOCK of a Pallas build with a class axis: how many
    classes one kernel call stacks.  The largest ``kb`` whose stacked
    left operand ``kb x A`` (``A = 2 x n_nodes x hi`` at the stack's
    ``lo``, :func:`_lo_stacked`) is no taller than the MXU
    (``_MXU_ROWS``) and which :func:`_pallas_ok` admits with a feature
    block of at least 8 rows (``whole``: all of ``n_rows``) — from
    shapes alone.  1, a call a class, where nothing can be stacked: no
    aligned ``lo``, one class's ``A`` already past half the array, or a
    build that one call does not take (node blocks)."""
    lo = _lo_stacked(n_nodes, n_bins)
    if (n_class == 1 or not lo or _node_block(
            n_bins, n_rows, n_nodes, bins_itemsize, whole) != n_nodes):
        return 1
    kb = min(n_class, _MXU_ROWS // (2 * n_nodes * -(-n_bins // lo)))
    while kb > 1:
        block = _pallas_ok(n_bins, n_rows, n_nodes, bins_itemsize,
                           n_class=kb)
        if block >= n_rows if whole else block > 0:
            break
        kb -= 1
    return max(kb, 1)


def hist_class_blocks(n_bins: int, n_rows: int, n_nodes: int,
                      n_class: int, bins_itemsize: int = 1,
                      whole: bool = False) -> tuple[int, ...]:
    """Classes of each kernel call of a Pallas build with a class axis
    of ``n_class``, in class order: whole blocks of :func:`_class_block`
    classes and the rest.  What :func:`build_histogram` traces and
    ``HistGBT.round_plan`` records (``hist_class_blocks``): the
    engagement counter of the stacked kernel.  Seven classes at 256
    bins, by the level's builds::

        n_build    1     2     4     8      16           32
        lo        32    64   128   128     128          128
        A         16    16    16    32      64          128
        blocks   (7,)  (7,)  (7,)  (4, 3)  (2, 2, 2, 1)  (1,) * 7

    One class is ``(1,)`` at every shape."""
    kb = _class_block(n_bins, n_rows, n_nodes, n_class, bins_itemsize,
                      whole)
    full, rest = divmod(n_class, kb)
    return (kb,) * full + ((rest,) if rest else ())


def pallas_interpret() -> bool:
    """Whether this module's ``pallas_call``s run in the Pallas
    INTERPRETER instead of being compiled by Mosaic: everywhere but on a
    TPU backend.  The one place that decision is made — the CPU test
    suite exercises kernel logic through it, and ``chip_smoke.py``
    requires it to be False."""
    return jax.default_backend() != "tpu"


def resolve_hist_method(method: str, n_bins: int, n_rows: int,
                        n_nodes: int = 1, bins_itemsize: int = 1,
                        whole: bool = False) -> str:
    """The histogram engine ``method`` stands for at this shape
    (``n_rows`` = rows of the feature-major matrix the kernel reads:
    features, or a layout's physical rows).  ``auto`` chooses from what
    it can observe — backend and the kernel's VMEM budgets: on a TPU
    ``pallas`` wherever :func:`_node_block` admits a block of nodes,
    which for a plain matrix is every width and every node count (the
    build runs feature block by feature block, node block by node
    block); ``whole`` says the matrix cannot be cut on features (a
    nibble-packed layout's packed rows lead the block), so all of
    ``n_rows`` have to fit at some node block.  An explicit method is
    returned as is, except that ``pallas`` at a shape the budgets
    refuse is an error, never a quiet ``segment``.
    ``models.histgbt`` calls this per tree level up front, so the
    choice is on record (``HistGBT.round_plan``) before anything
    traces."""
    fits = _node_block(n_bins, n_rows, n_nodes, bins_itemsize, whole) > 0
    if method == "auto":
        on_tpu = jax.default_backend() == "tpu"
        return "pallas" if on_tpu and fits else "segment"
    if method == "pallas" and not fits:
        block = _pallas_ok(n_bins, n_rows, 1, bins_itemsize)
        log_fatal(f"build_histogram: method='pallas' was requested but "
                  f"the kernel's VMEM budgets admit "
                  + (f"only {block} of the {n_rows} rows of a packed "
                     f"layout, which cannot be built in feature blocks,"
                     if block else "no feature block, not even 8 rows,")
                  + f" for even one node at n_bins={n_bins} (asked: "
                  f"n_nodes={n_nodes}), itemsize={bins_itemsize}, "
                  f"tile_rows={_TILE_ROWS} — use 'auto' or 'segment'")
    if method not in ("segment", "pallas"):
        log_fatal(f"build_histogram: unknown method {method!r}")
    return method


def build_histogram(
    bins: jax.Array,        # [n, F] uint8/int32 — binned feature matrix
    node_id: jax.Array,     # [n] int32 — tree-node assignment of each row
    grad: jax.Array,        # [n] f32
    hess: jax.Array,        # [n] f32
    n_nodes: int,
    n_bins: int,
    method: str = "auto",
    *,
    transposed: bool = False,
    layout=None,
    tile_live: jax.Array = None,
    n_features: int = 0,
) -> jax.Array:
    """Return ``hist[2, n_nodes, F, n_bins]`` — plane 0 Σgrad, plane 1 Σhess.

    Static ``n_nodes``/``n_bins`` keep shapes XLA-compilable; rows with
    ``node_id < 0`` (e.g. padding) contribute nothing.

    A CLASS axis: ``node_id``, ``grad``, ``hess`` all ``[K, n]`` (K
    trees over the same rows, each class its own node ids and
    gradients) return ``hist[K, 2, n_nodes, F, n_bins]``, class c's
    histogram bit for bit what its own call would build
    (:func:`_hist_class_blocks`).

    ``transposed=True`` means ``bins`` is already ``[F, n]`` — the Pallas
    kernel's native layout.  The training loop stores bins transposed so
    the per-level kernel never re-transposes the matrix (a full HBM
    round-trip per histogram otherwise).

    ``layout`` (a :class:`~dmlc_core_tpu.ops.binlayout.BinLayout`) means
    ``bins`` is the PHYSICAL ``[phys_rows, n]`` matrix (nibble-packed /
    bundled) and the result is the STORAGE-space histogram
    ``[2, n_nodes, S, layout.sync_bins]`` — callers unbundle/pad back to
    ``[2, N, F, n_bins]`` via ``binlayout.unbundle_hist`` before split
    evaluation.  The Pallas kernel reads packed bytes natively (the HBM
    win); ``segment`` unpacks to the storage matrix first (exact
    integer nibble extraction, so cell values stay bit-identical to an
    unpacked build — the cross-method parity contract).

    ``tile_live`` (``s32[grid]``, :func:`tile_liveness`) makes the
    TILE-SKIPPING build of the Pallas kernel: the operands come
    tile-aligned from :func:`tile_aligned` — ``bins`` ``[Fp, n_pad]`` of
    which ``n_features`` rows are real, ``node_id`` / ``grad`` /
    ``hess`` ``[n_pad]`` — nothing is padded again, and a tile whose
    entry is 0 (none of its rows has ``node_id >= 0``) is neither
    fetched nor computed (:func:`_hist_pallas_skip`).  The result is
    ``[2, n_nodes, n_features, n_bins]``.  Without it every call traces
    what it always traced.
    """
    if tile_live is not None:
        CHECK(transposed and layout is None and node_id.ndim == 1
              and method == "pallas",
              "tile_live= is the Pallas build of one tree over the plain "
              "transposed matrix")
        return _hist_pallas_blocks(bins, node_id, grad, hess, n_nodes,
                                   n_bins, transposed=True,
                                   tile_live=tile_live,
                                   n_features=n_features or bins.shape[0])
    if layout is not None:
        CHECK(transposed, "layout= requires the transposed [F, n] matrix")
        n_bins = layout.sync_bins
        method = resolve_hist_method(method, n_bins, layout.phys_rows,
                                     n_nodes, 1, whole=bool(layout.pairs))
        if method == "pallas":
            # a bundle-only layout is physical == storage: the plain
            # kernel; packed rows lead the block, so it is never cut on
            # features
            return _hist_class_blocks(
                bins, node_id, grad, hess, n_nodes, n_bins, transposed=True,
                layout=layout if layout.pairs else None)
        return _hist_segment_classes(_bl.unpack_matrix(bins, layout).T,
                                     node_id, grad, hess, n_nodes, n_bins)
    F = bins.shape[0] if transposed else bins.shape[1]
    method = resolve_hist_method(method, n_bins, F, n_nodes,
                                 jnp.dtype(bins.dtype).itemsize)
    if method == "segment":
        return _hist_segment_classes(bins.T if transposed else bins,
                                     node_id, grad, hess, n_nodes, n_bins)
    return _hist_class_blocks(bins, node_id, grad, hess, n_nodes, n_bins,
                              transposed=transposed)


def _hist_segment_classes(bins, node_id, grad, hess, n_nodes, n_bins):
    """:func:`_hist_segment`, over the class axis where there is one."""
    if node_id.ndim == 1:
        return _hist_segment(bins, node_id, grad, hess, n_nodes, n_bins)
    return jax.vmap(lambda nd, g, h: _hist_segment(
        bins, nd, g, h, n_nodes, n_bins))(node_id, grad, hess)


def _hist_class_blocks(bins, node_id, grad, hess, n_nodes, n_bins, *,
                       transposed, layout=None):
    """:func:`_hist_pallas_blocks` over the class blocks of
    :func:`hist_class_blocks`.  No class axis is the plain call and
    traces nothing else.  With one, ``node_id`` / ``grad`` / ``hess``
    ``[K, n]``: one call a block of classes, a block of ONE class the
    plain call on that class's rows, the histograms joined on the
    leading class axis.  A block of several is the STACKED call: per
    feature the kernel reads the bins block once, builds the right
    one-hot once and lays the classes' left one-hots — each from its own
    class's node ids, scaled by its own gradients — one under another
    for ONE dot.  A cell of a class's histogram is a row of that dot
    times a column of it: the sum of the same products over the same
    rows in the same tile order, whoever shares the dot and at whichever
    ``lo`` (``tests/test_hist_class_blocks.py``).  What class blocking
    adds outside the kernels (the slabs of classes, the join) runs
    under the device scope ``dmlc.hist.cblock``."""
    if node_id.ndim == 1:
        return _hist_pallas_blocks(bins, node_id, grad, hess, n_nodes,
                                   n_bins, transposed=transposed,
                                   layout=layout)
    rows = bins.shape[0] if transposed else bins.shape[1]
    n_class = node_id.shape[0]
    blocks = hist_class_blocks(n_bins, rows, n_nodes, n_class,
                               jnp.dtype(bins.dtype).itemsize,
                               whole=layout is not None)
    build = partial(_hist_pallas_blocks, n_nodes=n_nodes, n_bins=n_bins,
                    transposed=transposed, layout=layout)
    if blocks == (n_class,) and n_class > 1:
        return build(bins, node_id, grad, hess)
    with jax.named_scope("dmlc.hist.cblock"):
        # one class: the call it always was, on that class's rows
        slabs = [[a[lo] if hi - lo == 1 else a[lo:hi]
                  for a in (node_id, grad, hess)]
                 for lo, hi in pairwise(accumulate(blocks, initial=0))]
    hists = [build(bins, *slab) for slab in slabs]
    with jax.named_scope("dmlc.hist.cblock"):
        return jnp.concatenate([h if h.ndim == 5 else h[None]
                                for h in hists], axis=0)


def _hist_pallas_blocks(bins, node_id, grad, hess, n_nodes, n_bins, *,
                        transposed, layout=None, tile_live=None,
                        n_features=0):
    """:func:`_hist_pallas` over the node blocks of
    :func:`hist_node_blocks` and, inside each, the feature blocks of
    :func:`hist_feature_blocks`.  One block of each (every shape the
    one-call budgets admit) is the plain call and traces nothing else.

    Several NODE blocks: the same kernel once per block over ALL rows,
    the node ids of the block mapped to ``0..nb-1`` and every other row
    to ``-1`` (the kernel ignores ``node < 0``), the histograms joined
    on the node axis.  A node's sums depend on no other node, and a
    row's place in its tile and the tiles' order do not change, so each
    node's sums are made of the same operations in the same order as in
    an unblocked build, bit for bit — also where a block factors the
    bins with another ``lo`` than the whole build would (the rest block
    of a node count that is no power of two; at 256 bins every build of
    8 to 256 nodes factors alike): ``lo`` decides which dot a cell
    sits in, not what is added into it
    (``tests/test_hist_node_blocks.py`` holds both).  What node blocking
    adds outside the kernels (the per-block node maps, the join) runs
    under the device scope ``dmlc.hist.nblock``.  ``layout`` is a
    nibble-packed layout: cut on nodes like any other build, never on
    features.  A class axis (``node_id`` ``[Kb, n]``: a stacked call
    of :func:`_hist_class_blocks`) rides through: the maps are
    elementwise and the joins count their axes from the end.
    ``tile_live`` / ``n_features`` (a tile-skipping build,
    :func:`build_histogram`) ride through to every call: a tile without
    a row of the build has none of any node block's."""
    rows = n_features or (bins.shape[0] if transposed else bins.shape[1])
    skip = ({} if tile_live is None
            else {"tile_live": tile_live, "n_features": n_features})
    blocks = hist_node_blocks(n_bins, rows, n_nodes,
                              jnp.dtype(bins.dtype).itemsize,
                              whole=layout is not None)
    if len(blocks) == 1:
        return _hist_pallas_fblocks(bins, node_id, grad, hess, n_nodes,
                                    n_bins, transposed=transposed,
                                    layout=layout, **skip)
    in_nblock = jax.named_scope("dmlc.hist.nblock")
    own = in_nblock(lambda lo, hi: jnp.where(
        (node_id >= lo) & (node_id < hi), node_id - lo, -1))
    return in_nblock(jnp.concatenate)(
        [_hist_pallas_fblocks(bins, own(lo, hi), grad, hess, hi - lo,
                              n_bins, transposed=transposed, layout=layout,
                              **skip)
         for lo, hi in pairwise(accumulate(blocks, initial=0))], axis=-3)


def _hist_pallas_fblocks(bins, node_id, grad, hess, n_nodes, n_bins, *,
                         transposed, layout=None, tile_live=None,
                         n_features=0):
    """One node block of :func:`_hist_pallas_blocks` over its feature
    blocks.  One block (every shape the whole-matrix budgets admit, and
    every packed ``layout``) is the plain call and traces nothing else.
    Several: the same kernel once per contiguous slab of feature rows,
    the histograms joined on the feature axis — a feature's sums depend
    on no other feature, so each is made of the same operations in the
    same order as in an unblocked build, bit for bit.  What blocking
    adds outside the kernels (the slabs, the join) runs under the device
    scope ``dmlc.hist.fblock``.  Every call of a build cut on features
    states ``_NESTED_BLOCKS_VMEM`` for itself (a stacked call keeps its
    ``_STACKED_VMEM``): the limit is stated, not used — the kernel is
    the same kernel.  A tile-skipping build (``tile_live``: the matrix
    comes with ``Fp`` rows, ``n_features`` of them real) cuts its slabs
    to whole groups of 8 rows — the last block takes the pad rows with
    it — and every block's call takes the same liveness vector."""
    if layout is not None:
        return _hist_pallas(bins, node_id, grad, hess, n_nodes, n_bins,
                            transposed=True, layout=layout)
    F = n_features or (bins.shape[0] if transposed else bins.shape[1])
    blocks = hist_feature_blocks(
        n_bins, F, n_nodes, jnp.dtype(bins.dtype).itemsize,
        n_class=1 if node_id.ndim == 1 else node_id.shape[0])
    if tile_live is not None:
        def call(slab, fb, **kw):
            return _hist_pallas_skip(slab, node_id, grad, hess, tile_live,
                                     n_nodes, n_bins, fb, **kw)
    else:
        def call(slab, fb, **kw):
            return _hist_pallas(slab, node_id, grad, hess, n_nodes, n_bins,
                                transposed=transposed, **kw)
    if len(blocks) == 1:
        return call(bins, F)
    in_fblock = jax.named_scope("dmlc.hist.fblock")
    slab = in_fblock(lambda lo, hi: (
        bins[lo:-(-hi // 8) * 8] if tile_live is not None
        else bins[lo:hi] if transposed else bins[:, lo:hi]))
    return in_fblock(jnp.concatenate)(
        [call(slab(lo, hi), hi - lo, vmem_limit_bytes=_NESTED_BLOCKS_VMEM)
         for lo, hi in pairwise(accumulate(blocks, initial=0))], axis=-2)


@partial(jax.jit, static_argnums=(4, 5))
def _hist_segment(bins, node_id, grad, hess, n_nodes, n_bins):
    n, F = bins.shape
    valid = node_id >= 0
    safe_node = jnp.where(valid, node_id, 0)
    # combined segment id per (row, feature)
    feat_ids = jnp.arange(F, dtype=jnp.int32)[None, :]                    # [1, F]
    seg = (safe_node[:, None] * (F * n_bins)
           + feat_ids * n_bins
           + bins.astype(jnp.int32))                                      # [n, F]
    num = n_nodes * F * n_bins
    seg_flat = seg.reshape(n * F)

    def one(v):
        data = jnp.broadcast_to(jnp.where(valid, v, 0.0)[:, None], (n, F))
        return jax.ops.segment_sum(data.reshape(n * F), seg_flat, num_segments=num)

    return jnp.stack([one(grad), one(hess)]).reshape(2, n_nodes, F, n_bins)


def _hist_pallas_kernel(bins_ref, node_ref, g_ref, h_ref, out_ref,
                        *, n_nodes, hi, lo, n_rows, n_pack_groups=0):
    """One row-tile of the FACTORED one-hot matmul.

    bin = hi_part·lo + lo_part.  Per feature, ONE MXU dot
    ``[A, T] · [lo, T]ᵀ`` where A = 2·N·hi one-hot sublanes encode
    (grad/hess plane, node, hi_part) scaled by g/h and the RHS encodes
    lo_part.  A narrow dot does not pad to 128 sublanes on v5e (it
    costs ~A/128 of a full pass), so a level costs what its A asks for
    and the one-hot construction is the per-level floor (sibling
    subtraction at the call site halves A again).  One-hots live only
    in VMEM values (never HBM); HBM traffic is the bin matrix itself.

    Layout: everything arrives TRANSPOSED (rows on lanes — bins [F, T],
    node/g/h [1, T]) so the per-feature loop can be a fori_loop that
    dynamically slices the ref's major dim; a Python unroll over 28
    features blows the scoped-vmem stack, and Mosaic lowers neither
    dynamic_slice on values nor lane-dim dynamic ref slices.  Vector
    compares run in int32 (bf16/int16 compares rejected by this target).

    ``n_rows`` is the matrix's REAL row count: the block is padded to
    ``Fp`` rows (a multiple of 8) with zeros, and a pad FEATURE is not
    free the way a padded ROW (``node = -1``, matches nothing) is — its
    rows all sit in bin 0, so its compare, scalings and dot would cost
    exactly what a real feature's do, for sums the caller drops.
    :func:`_accum_hist` stops at ``n_rows``.

    A STACKED call: node/g/h arrive ``[Kb, T]``, a class a row, and the
    left operand is the Kb classes' ``[A, T]`` one-hots one under
    another, ``[Kb·A, T]`` — one dot a feature for all of them, against
    the one right operand, which reads the bins alone.  Kb = 1 is the
    kernel above, operation for operation.
    """
    i = pl.program_id(0)

    def rows(ref, dtype):
        # a class's [1, T] row, cut out in 32 bits (bf16 packs two rows
        # a sublane) and converted after
        a = ref[:]                                                    # [Kb, T]
        if a.shape[0] == 1:
            return [a.astype(dtype)]
        return [a[c:c + 1].astype(dtype) for c in range(a.shape[0])]

    node = rows(node_ref, jnp.int32)
    g = rows(g_ref, jnp.bfloat16)
    h = rows(h_ref, jnp.bfloat16)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    _accum_hist(bins_ref, out_ref, node, g, h,
                n_nodes=n_nodes, hi=hi, lo=lo, n_rows=n_rows,
                n_pack_groups=n_pack_groups)


def _hist_pallas_skip_kernel(live_ref, src_ref, bins_ref, node_ref, g_ref,
                             h_ref, out_ref, *, n_nodes, hi, lo, n_rows):
    """:func:`_hist_pallas_kernel` of one class over tile-aligned
    operands (:func:`_hist_pallas_skip`), with two scalar-prefetch
    vectors a grid step reads before its blocks are fetched:
    ``live_ref[i]`` — whether tile ``i`` holds a
    row of the build — gates the accumulation, and ``src_ref[i]`` is the
    tile whose blocks step ``i`` names (its own where it is live, else
    the live tile before it: the pipeline fetches no block whose index
    did not change, so a dead step moves no bytes).  A live tile adds
    what it always added, in the same tile order; a dead one held rows
    of ``node < 0`` alone and added exact zeros."""
    del src_ref                              # the index maps read it
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(live_ref[i] != 0)
    def _():
        _accum_hist(bins_ref, out_ref, [node_ref[:].astype(jnp.int32)],
                    [g_ref[:].astype(jnp.bfloat16)],
                    [h_ref[:].astype(jnp.bfloat16)],
                    n_nodes=n_nodes, hi=hi, lo=lo, n_rows=n_rows)


def _accum_hist(bins_ref, out_ref, node, g, h, *, n_nodes, hi, lo, n_rows,
                n_pack_groups=0):
    """The histogram accumulation loop of :func:`_hist_pallas_kernel`.
    ``node`` / ``g`` / ``h`` are lists of the stacked classes' ``[1, T]``
    rows (one class: lists of one).

    ``n_pack_groups`` > 0 marks the first ``8·n_pack_groups`` physical
    rows as NIBBLE-PACKED (two int4 storage features per byte, see
    ops/binlayout.py): each packed physical row emits TWO logical
    output rows — low nibble to ``2r``, high nibble to ``2r+1`` — so
    one HBM byte feeds two features' one-hot dots (the packed-bin HBM
    win).  The unpacked remainder follows at logical offset
    ``16·n_pack_groups``.  With ``n_pack_groups == 0`` the trace is
    IDENTICAL to the pre-layout kernel (the packed loop is not even
    traced), preserving bit-parity for the default path.

    ``n_rows`` of the block's ``Fp`` rows are real; the zero rows that
    pad the unpacked region to a multiple of 8 are pad FEATURES.  A dot
    is emitted only for a real row: the loop runs the whole groups of 8
    and ONE static tail group unrolls the ``n_rows % 8`` that remain
    (it reads the same aligned ``[8, T]`` block — the pad is still
    there to be read, just not multiplied).  Output rows past the last
    real one keep the zeros of the ``i == 0`` init.  At
    ``n_rows % 8 == 0`` there is no tail and the trace is the one this
    loop always had; every real row's sum is made of the same
    operations in the same order either way.
    """
    T = bins_ref.shape[1]
    nh = n_nodes * hi
    nh_iota = jax.lax.broadcasted_iota(jnp.int32, (nh, T), 0)
    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (lo, T), 0)
    t0_node = []
    for node_c in node:
        valid = node_c >= 0
        t0_node.append(jnp.where(valid, jnp.where(valid, node_c, 0) * hi,
                                 jnp.int32(-(1 << 20))))              # [1, T]

    def emit(t0s, los, k, row):
        # per class ONE [nh, T] compare then scale by g and h (the
        # grad/hess planes share the one-hot) — 2× cheaper than
        # comparing a [2·nh, T] iota twice.  compare→astype→mul (NOT
        # where): Mosaic can't relayout an i1 mask against a [1, T]-
        # replicated where operand.  The classes' planes one under
        # another; the right one-hot once for all of them.
        planes = []
        for t0s_c, g_c, h_c in zip(t0s, g, h):
            oh = (nh_iota == t0s_c[k:k + 1]).astype(jnp.bfloat16)     # [nh, T]
            planes += [oh * g_c, oh * h_c]
        lhs = jnp.concatenate(planes, axis=0)                    # [Kb·2nh, T]
        rhs = (lo_iota == los[k:k + 1]).astype(jnp.bfloat16)          # [lo, T]
        d = jax.lax.dot_general(
            lhs, rhs,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                         # [Kb·2nh, lo]
        idx = (pl.ds(row, 1), slice(None), slice(None))
        out_ref[idx] = out_ref[idx] + d[None]

    if n_pack_groups:
        def pbody(fg, carry):
            base = pl.multiple_of(fg * 8, 8)
            blk = bins_ref[pl.ds(base, 8), :].astype(jnp.int32)       # [8, T]
            for nb, vals in ((0, blk & 15), (1, blk >> 4)):
                his = vals // lo                                      # [8, T]
                t0s = [t0_c + his for t0_c in t0_node]
                los = vals % lo                                       # [8, T]
                for k in range(8):
                    emit(t0s, los, k, 2 * (fg * 8 + k) + nb)
            return carry

        jax.lax.fori_loop(0, n_pack_groups, pbody, 0)
    log_off = 16 * n_pack_groups

    def group(base, row, n_live):
        # feature GROUPS of 8: sublane-dim ref slices must be 8-aligned;
        # within a group a static unroll — a full 28-feature unroll
        # blows the scoped-vmem stack.  The integer prep runs BATCHED on
        # [8, T] (a [1, T] op costs the same VPU tiles as [8, T] —
        # sublane padding), only the one-hot compares are per-feature.
        blk = bins_ref[pl.ds(base, 8), :].astype(jnp.int32)           # [8, T]
        # padding rows carry t0_node ≈ -2^20 → t0 < 0 → match nothing
        his = blk // lo                                               # [8, T]
        t0s = [t0_c + his for t0_c in t0_node]
        los = blk % lo                                                # [8, T]
        for k in range(n_live):
            emit(t0s, los, k, row(k))

    def body(fg, carry):
        # pl.multiple_of proves the dynamic slice's alignment
        group(pl.multiple_of(fg * 8 + 8 * n_pack_groups if n_pack_groups
                             else fg * 8, 8),
              lambda k: (log_off + fg * 8 + k if n_pack_groups
                         else fg * 8 + k), 8)
        return carry

    full, rem = divmod(n_rows - 8 * n_pack_groups, 8)
    jax.lax.fori_loop(0, full, body, 0)
    if rem:
        group(8 * (n_pack_groups + full),
              lambda k: log_off + 8 * full + k, rem)


#: measured-best lo per n_build at n_bins=256 on v5e, tile 16384, 10M
#: rows (scripts/sweep_hist.py, 48-config sweep): the analytic 5A+2lo
#: model below agrees except n_build=2, where hardware prefers 32 over
#: the model's 64 (12.7 vs 14.9 ms).
_LO_MEASURED_256 = {1: 32, 2: 32, 4: 64, 8: 128, 16: 128}


def _lo_factor(n_nodes: int, n_bins: int) -> int:
    """Bin-factor split ``bin = hi·lo + lo_part``.  MXU work A·lo =
    2·N·n_bins is invariant in ``lo``, but the per-feature construction
    is ~c₁·A (LHS one-hots) + c₂·lo (RHS one-hot), so small ``lo``
    trades RHS compare traffic for LHS height.  At the default
    n_bins=256 the choice is pinned by measurement (sweep table above);
    other bin counts fall back to the op-count model, whose knee matched
    v5e hardware at every level except one."""
    if n_bins == 256 and n_nodes in _LO_MEASURED_256:
        return _LO_MEASURED_256[n_nodes]
    best, best_cost = 128, None
    for lo in (32, 64, 128):
        if lo > max(n_bins, 8):
            continue
        hi = -(-n_bins // lo)
        A = 2 * n_nodes * hi
        cost = 5 * A + 2 * lo          # construction op counts per element
        if best_cost is None or cost < best_cost:
            best, best_cost = lo, cost
    return best


def _lo_stacked(n_nodes: int, n_bins: int) -> int:
    """``lo`` of a call that stacks classes (:func:`hist_class_blocks`):
    the widest right operand of 128 / 64 / 32 — so the shortest left
    operand a class, ``A = 2·N·hi``, and the most classes in the MXU's
    128 rows — at which a class's ``nh = N·hi`` is a multiple of 8, so
    that every class's one-hot starts on a sublane tile of the stack.
    At 256 bins: 32 for one node, 64 for two, 128 from four on.  0 where
    none of the three does (few nodes at few bins): such a build is not
    stacked.  A cell's sums do not depend on ``lo``
    (:func:`_hist_pallas_blocks`)."""
    for lo in (128, 64, 32):
        if lo <= n_bins and (n_nodes * -(-n_bins // lo)) % 8 == 0:
            return lo
    return 0


@partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9, 10))
def _hist_pallas(bins, node_id, grad, hess, n_nodes, n_bins,
                 tile_rows: int = _TILE_ROWS, lo: int = 0,
                 transposed: bool = False, layout=None,
                 vmem_limit_bytes: int = 0):
    """Pallas TPU path: grid over row tiles, all tiles accumulate into the
    same [F, A, lo] VMEM output block (sequential TPU grid ⇒ safe),
    then one small reshape/transpose yields [2, N, F, B].

    With a nibble-packed ``layout`` the input is the PHYSICAL matrix:
    the kernel's packed region emits two logical rows per byte row, the
    logical output rows are permuted back to STORAGE feature order, and
    the result is the storage-space histogram [2, N, S, Bs].

    ``vmem_limit_bytes``: the scoped-VMEM limit the call states for
    itself; 0 leaves the compiler's (``_SCOPED_VMEM``), as every build
    of one feature block does.

    ``node_id`` / ``grad`` / ``hess`` ``[Kb, n]`` make the STACKED call
    (:func:`_hist_class_blocks`): in-blocks ``(Kb, tile_rows)`` — class
    -major as the round program holds them, no re-layout — the out
    block ``[F, Kb·A, lo]`` at the stack's ``lo`` (:func:`_lo_stacked`),
    the result ``[Kb, 2, N, F, B]``.  ``[n]`` is the call of one class
    it always was, the same program.  (The TILE-SKIPPING call is a
    program of its own, :func:`_hist_pallas_skip`.)"""
    if transposed:
        F, n = bins.shape
    else:
        n, F = bins.shape
    stack = node_id.shape[:-1]            # () or (Kb,)
    n_class = stack[0] if stack else 1
    lo = min(lo or (_lo_stacked(n_nodes, n_bins) if stack
                    else _lo_factor(n_nodes, n_bins)), n_bins)
    CHECK(lo > 0, "a stacked histogram call needs an aligned lo")
    if stack:
        vmem_limit_bytes = max(vmem_limit_bytes, _STACKED_VMEM)
    hi = -(-n_bins // lo)
    A = 2 * n_nodes * hi * n_class        # rows of the left operand
    Fp = -(-F // 8) * 8          # feature groups of 8 (sublane alignment)
    npg = 0
    if layout is not None:
        npg = layout.packed_rows // 8          # packed physical groups
        # logical rows: 2 per packed physical row + the unpacked rest
        L = 16 * npg + (Fp - 8 * npg)
    else:
        L = Fp
    pad = (-n) % tile_rows
    n_pad = n + pad
    grid = n_pad // tile_rows
    with jax.named_scope("dmlc.hist.pad"):
        if pad:
            row_pad = [(0, 0)] * len(stack) + [(0, pad)]
            node_id = jnp.pad(node_id, row_pad, constant_values=-1)
            grad = jnp.pad(grad, row_pad)
            hess = jnp.pad(hess, row_pad)
        if transposed:
            bins_t = jnp.pad(bins, ((0, Fp - F), (0, pad)))
        else:
            bins_t = jnp.pad(bins.T, ((0, Fp - F), (0, pad)))

    out = pl.pallas_call(
        partial(_hist_pallas_kernel, n_nodes=n_nodes, hi=hi, lo=lo,
                n_rows=F, n_pack_groups=npg),
        out_shape=jax.ShapeDtypeStruct((L, A, lo), jnp.float32),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((Fp, tile_rows), lambda i: (0, i)),
            pl.BlockSpec((n_class, tile_rows), lambda i: (0, i)),
            pl.BlockSpec((n_class, tile_rows), lambda i: (0, i)),
            pl.BlockSpec((n_class, tile_rows), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((L, A, lo), lambda i: (0, 0, 0)),
        interpret=pallas_interpret(),
        name="dmlc_hist",
        **({"compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes)} if vmem_limit_bytes else {}),
    )(bins_t, node_id.reshape(n_class, n_pad), grad.reshape(n_class, n_pad),
      hess.reshape(n_class, n_pad))
    with jax.named_scope("dmlc.hist.unpack"):
        if layout is not None:
            # kernel-logical rows → storage order (static permutation)
            perm = _bl.layout_tables(layout)["logical"]
            out = out.reshape(L, *stack, 2, n_nodes,
                              hi * lo)[jnp.asarray(perm)]
        else:
            out = out[:F].reshape(F, *stack, 2, n_nodes, hi * lo)
        # [F, (Kb,) gh, N, hi·lo] → [(Kb,) gh, N, F, hi·lo] → slice the
        # bin pads
        return out.transpose(*range(1, out.ndim - 1), 0,
                             out.ndim - 1)[..., :n_bins]


@partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _hist_pallas_skip(bins, node_id, grad, hess, tile_live, n_nodes, n_bins,
                      n_features, vmem_limit_bytes=0):
    """The TILE-SKIPPING call: :func:`_hist_pallas` of one class over
    operands the caller made tile-aligned (:func:`tile_aligned` —
    ``bins`` ``[Fp, n_pad]`` with ``n_features`` real rows, the row
    vectors ``[n_pad]``, pad rows ``node = -1``), so ``dmlc.hist.pad``
    copies nothing; the tile is ``n_pad / len(tile_live)`` rows.  The
    grid still has a step a tile, but a step whose ``tile_live`` is 0
    names the blocks of the live tile before it (of the first live tile
    where none came before) and skips the accumulation: it costs a grid
    step, not a fetch of the tile and its dots.  The kernel keeps its
    name, ``dmlc_hist``.  ``[2, N, n_features, B]``, every live tile's
    sums what the plain call adds for it, in the same order."""
    Fp, n_pad = bins.shape
    grid = tile_live.shape[0]
    tile_rows = n_pad // grid
    CHECK(Fp % 8 == 0 and 0 <= Fp - n_features < 8
          and grid * tile_rows == n_pad and node_id.ndim == 1,
          "a tile-skipping call takes one class of tile-aligned operands")
    lo = min(_lo_factor(n_nodes, n_bins), n_bins)
    hi = -(-n_bins // lo)
    A = 2 * n_nodes * hi
    with jax.named_scope("dmlc.hist.pad"):
        # where each step's blocks come from: its own tile, or the last
        # live one at or before it (the first live one where none is)
        last = jax.lax.cummax(jnp.where(
            tile_live != 0, jnp.arange(grid, dtype=jnp.int32), -1))
        src = jnp.where(last >= 0, last,
                        jnp.argmax(tile_live != 0).astype(jnp.int32))
    rows = pl.BlockSpec((1, tile_rows), lambda i, live, src: (0, src[i]))
    out = pl.pallas_call(
        partial(_hist_pallas_skip_kernel, n_nodes=n_nodes, hi=hi, lo=lo,
                n_rows=n_features),
        out_shape=jax.ShapeDtypeStruct((Fp, A, lo), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(grid,),
            in_specs=[pl.BlockSpec((Fp, tile_rows),
                                   lambda i, live, src: (0, src[i])),
                      rows, rows, rows],
            out_specs=pl.BlockSpec((Fp, A, lo),
                                   lambda i, live, src: (0, 0, 0)),
        ),
        interpret=pallas_interpret(),
        name="dmlc_hist",
        **({"compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes)} if vmem_limit_bytes else {}),
    )(tile_live, src, bins, node_id.reshape(1, n_pad),
      grad.reshape(1, n_pad), hess.reshape(1, n_pad))
    with jax.named_scope("dmlc.hist.unpack"):
        out = out[:n_features].reshape(n_features, 2, n_nodes, hi * lo)
        return out.transpose(1, 2, 0, 3)[..., :n_bins]


#: rows a device from which a leaf-wise tree re-clusters its rows
#: (:func:`recluster_points`).  The re-cluster costs a sort of every row
#: and saves, per later build, the tiles that hold none of the node's
#: rows: a cluster has to span many tiles of ``_TILE_ROWS`` rows for
#: that to be most of them.  2^21 rows a device are 128 tiles (the size
#: from which ``ops.table_select`` takes its per-row savings too);
#: measured at 24M rows only (PERF.md section 6, PR 57)
RECLUSTER_MIN_ROWS = 1 << 21
#: expansions a re-cluster has to be followed by to be taken: its sort,
#: side pass and way back to input order are ~0.44 s at 24M x 28 on v5e,
#: what ~22 later expansions save (a build of all rows 20.6 ms against
#: ~2 of a cluster's tiles: PERF.md section 5, PR 57), and its program
#: compiles for minutes; half as much again keeps a margin
_RECLUSTER_COST_BUILDS = 32


#: the widest matrix a tree re-clusters: its rows ride through the sort
#: four feature rows a ``uint32`` operand (:func:`recluster_rows`), and
#: a sort COMPILES for ~15 s an operand at 24M rows (PERF.md section 5,
#: PR 57): 16 words, four minutes
_RECLUSTER_MAX_FEATURES = 64


def recluster_points(max_leaves: int, rows_per_device: int,
                     n_features: int) -> tuple[int, ...]:
    """The expansions BEFORE which a leaf-wise tree of ``max_leaves``
    re-orders a device's rows by the leaf they sit in, from the shapes
    the program can see; ``()`` where it never does (today's one scan
    over all rows).  One point, ``s = sqrt(max_leaves - 1) / 2``,
    rounded: the ``s`` builds before it sweep all rows and each of the
    ``max_leaves - 1 - s`` after it the tiles of its leaf's cluster, a
    share of the rows that falls with ``s``, so the cost has a minimum
    in ``s`` that grows like a root of the budget.  At 255 leaves on
    24M x 28 (v5e, seconds a round; PERF.md section 5, PR 57)::

        s             8       16      32      (8, 64)
        a round     1.623   1.682   1.845     1.777     (6.893 unclustered)

    two points re-sort for less than the second sort costs."""
    s = max(int((max_leaves - 1) ** 0.5 / 2 + 0.5), 1)
    if (rows_per_device < RECLUSTER_MIN_ROWS
            or n_features > _RECLUSTER_MAX_FEATURES
            or max_leaves - 1 - s <= _RECLUSTER_COST_BUILDS):
        return ()
    return (s,)


def tile_aligned(bins_t, node_id, grad, hess):
    """The operands of a tile-skipping build (:func:`build_histogram`'s
    ``tile_live``), made once: the ``[F, n]`` matrix padded to
    ``[Fp, n_pad]`` (features to whole groups of 8, rows to whole tiles
    of ``_TILE_ROWS``) and the row vectors to ``[n_pad]``, pad rows
    ``node = -1`` — what :func:`_hist_pallas` pads on every call."""
    F, n = bins_t.shape
    pad = (-n) % _TILE_ROWS
    with jax.named_scope("dmlc.hist.pad"):
        return (jnp.pad(bins_t, ((0, (-F) % 8), (0, pad))),
                jnp.pad(node_id, (0, pad), constant_values=-1),
                jnp.pad(grad, (0, pad)), jnp.pad(hess, (0, pad)))


def hist_tile_rows() -> int:
    """Rows of one tile of the Pallas kernels' row grid (what a count of
    computed tiles is multiplied by)."""
    return _TILE_ROWS


def recluster_rows(key, bins_t, n_features, *rows_):
    """The tile-aligned rows of one device in the STABLE order of
    ``key`` (``s32[n_pad]``): ``(key, bins_t, *rows_)`` re-ordered alike.
    ONE ``lax.sort`` of one key with everything else as operands — the
    ``[Fp, n_pad]`` uint8 matrix rides as ``ceil(n_features / 4)``
    uint32 words a row, four feature rows a word (a sort's price is its
    operands: PERF.md section 5, PR 57); no per-row gather.  The pad
    features' rows come back zero."""
    Fp, n = bins_t.shape
    n_words = -(-n_features // 4)
    # row by row: a ``reshape`` of the uint8 matrix to ``[W, 4, n]``
    # does the same and compiles for minutes at 24M rows
    rows = [bins_t[i].astype(jnp.uint32) for i in range(4 * n_words)]
    words = [rows[i] | (rows[i + 1] << 8) | (rows[i + 2] << 16)
             | (rows[i + 3] << 24) for i in range(0, 4 * n_words, 4)]
    out = jax.lax.sort((key, *words, *rows_), num_keys=1, is_stable=True)
    rows = [((w >> s) & 255).astype(jnp.uint8)
            for w in out[1:1 + n_words] for s in (0, 8, 16, 24)]
    rows += [jnp.zeros_like(rows[0])] * (Fp - len(rows))
    return (out[0], jnp.stack(rows), *out[1 + n_words:])


def tile_liveness(node_build):
    """``s32[grid]``: 1 for each tile of ``_TILE_ROWS`` rows of a
    tile-aligned ``node_build`` that holds a row of the build
    (``>= 0``)."""
    return (node_build >= 0).reshape(-1, _TILE_ROWS).any(axis=1).astype(
        jnp.int32)


def descend_histogram(
    bins_t: jax.Array,      # [F, n] — transposed binned matrix
    node_id: jax.Array,     # [n] — node ids at level ℓ−1 (−1 = padding)
    feat_sel: jax.Array,    # [n] — each row's node's chosen split feature
    thr_sel: jax.Array,     # [n] — chosen split threshold (bin index)
    grad: jax.Array,
    hess: jax.Array,
    n_prev: int,            # number of level-(ℓ−1) nodes
    n_bins: int,
    method: str = "auto",
    dir_sel: jax.Array = None,  # [n] learned missing direction (1=left)
    miss_bin: int = None,       # bin index reserved for NaN rows
    layout=None,                # BinLayout: bins_t is the physical matrix
    go_right: jax.Array = None,  # [n] the caller has routed the rows
):
    """Advance rows one level down the tree and build the new level's
    LEFT-child histograms, in two passes over the bin matrix: an XLA
    descend (:func:`select_feature_bins`), then :func:`build_histogram`.
    Returns ``(left_hist, new_node)`` with ``left_hist[_, p]`` the
    histogram of parent p's left child (node 2p) — the caller derives
    the right child by sibling subtraction.  The level of the round
    program at every shape.  With a CLASS axis (every per-row array
    ``[K, n]``: the level of a multiclass round's K trees) the descend
    is each class's own and the build ONE :func:`build_histogram` of K
    classes; ``left_hist`` and ``new_node`` lead with K.
    ``go_right`` given, the caller has read each row's bin and decided
    its side (a categorical split goes by membership in a set, which a
    threshold cannot say) and the descend here only applies it.
    Replaces rabit's per-level hist allreduce prep (SURVEY.md §2e
    data-parallel row)."""
    valid = node_id >= 0
    if go_right is None:
        select = partial(select_feature_bins, bins_t, layout=layout)
        row_bin = (select if node_id.ndim == 1
                   else jax.vmap(select))(feat_sel)
        go_right = row_bin > thr_sel
    if dir_sel is not None:
        # learned missing direction: NaN rows (bin == miss_bin) follow
        # their node's dir bit (1 = left) instead of the threshold
        go_right = jnp.where(row_bin == miss_bin, dir_sel == 0, go_right)
    new_node = jnp.where(valid, 2 * node_id + go_right, -1)
    node_h = jnp.where(valid & (new_node % 2 == 0), new_node >> 1, -1)
    hist = build_histogram(bins_t, node_h, grad, hess, n_prev, n_bins,
                           method, transposed=True, layout=layout)
    return hist, new_node


def select_feature_bins(bins_t: jax.Array, feat_sel: jax.Array,
                        layout=None) -> jax.Array:
    """``bins_t[feat_sel[r], r]`` for every row r, gather-free.

    ``bins_t`` is feature-major [F, n]; a per-row gather over the row
    dimension serializes badly on TPU, so the selected feature's bin is
    extracted by compare-and-sum over the F rows (one [F, n] VPU pass).
    Shared by the tree descend in HistGBT (in-core and external-memory)
    and :func:`descend_histogram`.  With ``layout``
    the matrix is physical (packed/bundled) and ``feat_sel`` indexes
    ORIGINAL features — ``binlayout.select_bins`` decodes nibbles and
    bundle segments after the same compare-and-sum pass.
    """
    if layout is not None:
        return _bl.select_bins(bins_t, feat_sel, layout)
    f_iota = jnp.arange(bins_t.shape[0], dtype=jnp.int32)[:, None]
    return jnp.sum(jnp.where(feat_sel[None, :] == f_iota,
                             bins_t.astype(jnp.int32), 0), axis=0)


def reference_histogram(bins, node_id, grad, hess, n_nodes, n_bins):
    """Numpy oracle for tests — same [2, N, F, B] shape as build_histogram."""
    bins = np.asarray(bins)
    node_id = np.asarray(node_id)
    out = np.zeros((2, n_nodes, bins.shape[1], n_bins), np.float64)
    for i in range(bins.shape[0]):
        if node_id[i] < 0:
            continue
        for f in range(bins.shape[1]):
            out[0, node_id[i], f, bins[i, f]] += grad[i]
            out[1, node_id[i], f, bins[i, f]] += hess[i]
    return out.astype(np.float32)
