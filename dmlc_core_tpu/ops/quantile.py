"""Distributed weighted quantile binning (the sketch layer).

XGBoost's hist method bins features at per-feature (weighted) quantile cut
points, merged across workers.  The reference world does this with
variable-size quantile sketches allreduced over rabit (BASELINE config 3's
hard part).  The TPU-native design replaces the variable-size merge with a
**fixed-size summary + allgather-merge** (SURVEY.md §7 hard part (c)):

1. each worker summarizes every feature with a fixed grid of
   ``n_summary`` weighted quantiles of its local rows — fixed shape
   ``[F, n_summary]``, psum/allgather-friendly;
2. summaries are allgathered (one XLA AllGather over ICI instead of a
   variable-size sketch protocol);
3. the merged multiset of summary points is re-quantiled into ``n_bins-1``
   cut points, identically on every worker (deterministic, no broadcast
   needed).

**Error bound** (the fixed-size analogue of GK/WQSummary's ε guarantee):
every summarization stage approximates a weighted quantile function by
``S = n_summary`` points on an even probability grid with midpoint
interpolation, so reconstructing any quantile from one stage incurs rank
error ≤ 1/(S−1) (≈ 1/(2(S−1)) typically — the grid midpoint rule).
Stage errors add.  A value streamed through the accumulator passes
through: 1 page summary + ≤ ⌈log_C P⌉ ladder merges (C =
``buffer_pages``, P = pages seen; see :class:`SketchAccumulator`) +
1 cross-level merge (``summary()``) + 1 cross-worker collapse
(``finalize`` re-quantiles the gathered summaries even single-worker) +
1 final re-quantile into bins, giving

    eps(S, P, C)  ≤  (⌈log_C P⌉ + 4) / (S − 1)

rank error per cut — conservative by ~2× (midpoint rule).  At the
defaults (S = 8·n_bins = 2048, C = 32) even a million pages stay under
(4+4)/2047 ≈ 0.0039 ≈ 1.0 bin width at 256 bins, and realistic page
counts (≤ 32k) under 0.7 bin widths.  ``tests/test_external_memory.py``
property-checks this bound against adversarial distributions
(heavy-tail, atom-dominated, 10⁶:1 weight skew, sorted streams).

Device phases (doc/observability.md): everything that computes cuts is
traced under ``jax.named_scope("dmlc.cuts")``, digitizing under
``"dmlc.bin"`` — one name for ingest and predict, it is one function.

A CATEGORICAL column has no cuts: its values are codes, its row of the
``[F, n_bins-1]`` cut matrix is its category→bin TABLE (the code of bin
``k`` at position ``k``, -1 past the last named bin: :func:`cat_tables`,
device scope ``dmlc.cats``) and it is binned by lookup
(:func:`apply_bins_t` with ``cat=``, ``dmlc.bin.cat``).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dmlc_core_tpu.base.logging import CHECK
from dmlc_core_tpu.base.parameter import get_env

__all__ = ["local_summary", "mesh_summary", "merge_summaries", "compute_cuts",
           "apply_bins", "apply_bins_t", "apply_bins_missing",
           "SketchAccumulator", "nan_scan", "mesh_nan_scan",
           "CAT_MAX_CODE", "cat_scan", "cat_tables", "cat_bins_used"]


def _key_sort_quantiles(x: jax.Array, qs: jax.Array) -> jax.Array:
    """``jnp.quantile(x, qs, axis=0)`` (linear) → ``[len(qs), F]``, with
    a sort of the KEYS ALONE in the middle.

    ``jnp.quantile`` sorts with ``lax.sort``'s default ``is_stable=True``;
    the TPU compiler keeps a one-operand sort stable by sorting a second
    operand beside it, an ``s32[n, F]`` row index from an ``iota`` that
    nothing reads afterwards (at 24M x 28 one more 2.86 GiB matrix of
    temporaries and half the sort's time, PERF.md section 6, PR 38).
    For keys alone stability has no meaning: the float comparator is a
    total order (NaN and -0.0 canonicalised, then the bit patterns as
    integers), so keys that compare equal ARE the same float32 and the
    sorted column is the same column whichever way ties fall.  The
    exceptions are the zeros: -0.0 and +0.0 compare equal, and so do the
    denormals, which XLA compares as zero on the CPU and the TPU alike.
    They may swap places; the interpolation flushes a denormal to zero,
    so at most a zero's sign moves in the summary, and
    :func:`merge_summaries`' guard adds +0.0 to every cut: the cuts are
    the same bits.

    Everything around the sort is ``_quantile``'s, operation for
    operation and in its order — the all-NaN guard, ``q * (n - 1)`` in
    float32, floor / ceil, the clamp, two row gathers of ``[len(qs),
    F]``, ``low * (1 - w) + high * w`` — so the result equals
    ``jnp.quantile``'s to the bit (``tests/test_models.py::TestQuantile``).
    """
    n = x.shape[0]
    x = jnp.where(jnp.any(jnp.isnan(x), axis=0, keepdims=True), jnp.nan, x)
    xs = jax.lax.sort(x, dimension=0, is_stable=False)
    last = jax.lax.convert_element_type(n, qs.dtype) - 1
    q = qs * last
    low, high = jax.lax.floor(q), jax.lax.ceil(q)
    high_weight = q - low
    low_weight = 1 - high_weight
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(1,), collapsed_slice_dims=(0,), start_index_map=(0,))

    def rows(at):                                          # [len(qs), F]
        at = jax.lax.clamp(0.0, at, last).astype(jnp.int32)
        return jax.lax.gather(xs, at[:, None], dnums, (1, x.shape[1]),
                              mode="promise_in_bounds")

    return (rows(low) * low_weight[:, None]
            + rows(high) * high_weight[:, None])


def _finite_key_sort_summary(x: jax.Array, n_summary: int) -> jax.Array:
    """``[F, n_summary]`` midpoint-rule quantiles of each column's
    non-NaN values, from a sort of the keys alone.

    Without row weights a value weighs 1 and a NaN 0, so the weighted
    quantile function needs no permutation: ``lax.sort`` puts NaN last
    (its comparator canonicalises NaN above +inf), the first ``c`` rows
    of a sorted column are its ``c`` non-NaN values in order, and value
    ``k`` sits at probability ``(k + 0.5) / c``.  The summary point of
    probability ``q = j / (S - 1)`` is therefore read at position
    ``t = q * c - 0.5``, clipped to ``[0, c - 1]`` (what ``jnp.interp``
    does at its ends), by linear interpolation between rows ``floor(t)``
    and ``floor(t) + 1``: ``2 * S * F`` gathered elements, where the
    weighted path gathers every element of the matrix twice
    (``argsort`` + two ``take_along_axis``; at 1,183,747 x 968 that
    path's stable index-carrying sort alone asks the v5e compiler for
    25.7 GiB of 15.75, PERF.md section 6, PR 42).

    ``t`` is computed exactly, in integers: with ``c = a * (S - 1) + b``,
    ``j * c / (S - 1) = j * a + (j * b) / (S - 1)`` — quotient ``k`` and
    remainder ``r`` — so ``t = k + r / (S - 1) - 0.5`` for any
    ``c < 2**31`` (``q * c`` in float32 loses the fraction beyond 2**24
    rows, and a sixteenth of a row at a million).  A column without a
    value (``c = 0``) gives the all-NaN sentinel row.
    """
    S = n_summary
    den = max(S - 1, 1)
    CHECK(S * S < 2 ** 31, "n_summary too large for the exact positions")
    with jax.named_scope("dmlc.cuts.finite"):
        c = jnp.sum(~jnp.isnan(x), axis=0, dtype=jnp.int32)           # [F]
        xs = jax.lax.sort(x, dimension=0, is_stable=False)
        j = jnp.arange(S, dtype=jnp.int32)[:, None]                   # [S, 1]
        a, b = c // den, c % den
        k = j * a[None, :] + (j * b[None, :]) // den                  # [S, F]
        r = (j * b[None, :]) % den
        upper = 2 * r >= den             # is the fraction of q*c >= 1/2
        low = jnp.where(upper, k, k - 1)
        w_high = (r.astype(x.dtype) / den
                  + jnp.where(upper, -0.5, 0.5).astype(x.dtype))
        last = jnp.maximum(c - 1, 0)[None, :]

        def rows(at):                                                 # [S, F]
            return jnp.take_along_axis(xs, jnp.clip(at, 0, last), axis=0,
                                       mode="promise_in_bounds")

        out = rows(low) * (1 - w_high) + rows(low + 1) * w_high
        return jnp.where((c == 0)[None, :], jnp.nan, out).T


def local_summary(x: jax.Array, weight: Optional[jax.Array],
                  n_summary: int, missing: bool = False) -> jax.Array:
    """Fixed-size weighted quantile summary of local rows.

    ``x``: [n, F] f32; ``weight``: [n] or None.  Returns [F, n_summary]
    (per-feature weighted quantiles on an even probability grid).

    Which paths carry a permutation, and why:

    * no weights, no ``missing`` — every dense fit, a whole-matrix sort:
      ``jnp.quantile(x, qs, axis=0).T`` to the bit, its sort of the keys
      ALONE (``is_stable=False``, :func:`_key_sort_quantiles`): a stable
      sort makes the TPU compiler carry an ``s32[n, F]`` row index
      through every pass, and no value of the sorted column depends on
      how ties fall;
    * no weights, ``missing=True`` — every fit of a table with holes:
      the midpoint-rule summary of each column's non-NaN values, again
      from a key-only sort (:func:`_finite_key_sort_summary`, device
      scope ``dmlc.cuts.finite``): weights of 0 and 1 need no
      permutation, the NaN sort last and the count of the others says
      where each quantile point lies;
    * ``weight`` given, with or without ``missing``: the weights have to
      follow their values through the sort, so this path keeps its
      permutation (``argsort``, two ``take_along_axis``, a cumsum and a
      ``vmap``ped ``interp``) — ROADMAP M8.

    ``missing=True`` excludes NaN entries from the summary.  On the
    weighted path they are rewritten to the feature's max finite value
    with weight 0 (the fixed-shape alternative to per-feature
    nan-filtering, which would break the [F, n_summary] contract when
    NaN counts differ by feature).  A feature with NO finite value on
    this worker emits an explicit all-NaN sentinel row (total weight
    0), which :func:`merge_summaries` excludes — a shard-local all-NaN
    column is legal in distributed fits as long as the feature is
    finite on SOME worker (callers enforce the global check, histgbt's
    finite_any allreduce).
    """
    n, F = x.shape
    qs = jnp.linspace(0.0, 1.0, n_summary)
    if weight is None:
        if missing:
            return _finite_key_sort_summary(x, n_summary)
        return _key_sort_quantiles(x, qs).T   # [F, n_summary]
    w2d = jnp.broadcast_to(weight[:, None], x.shape)
    if missing:
        nan = jnp.isnan(x)
        w2d = jnp.where(nan, 0.0, w2d)
        fmax = jnp.max(jnp.where(nan, -jnp.inf, x), axis=0)    # [F]
        x = jnp.where(nan, fmax[None, :], x)
    order = jnp.argsort(x, axis=0)                                    # [n, F]
    xs = jnp.take_along_axis(x, order, axis=0)
    ws = jnp.take_along_axis(w2d, order, axis=0)                      # [n, F]
    cw = jnp.cumsum(ws, axis=0)
    total = cw[-1:, :]
    probs = (cw - 0.5 * ws) / total                                   # midpoint rule
    def per_f(xf, pf):
        return jnp.interp(qs, pf, xf)
    out = jax.vmap(per_f, in_axes=(1, 1))(xs, probs)                  # [F, n_summary]
    if missing:
        # zero total weight = all-NaN column on this shard: the -inf/0-div
        # garbage above is made a deterministic NaN sentinel row here.
        out = jnp.where((total[0] <= 0.0)[:, None], jnp.nan, out)
    return out


def _scoped(fn, scope: str, static_argnums):
    """``fn`` as a program of its own whose operations carry the device
    scope ``scope``: the same function is ``dmlc.cuts`` where
    ``compute_cuts`` runs it on a whole matrix and ``dmlc.sketch.*``
    where :class:`SketchAccumulator` runs it on a page (the innermost
    scope names an operation, so one body cannot carry both)."""
    return jax.jit(jax.named_scope(scope)(fn), static_argnums=static_argnums)


_page_summary = _scoped(local_summary, "dmlc.sketch.add", (2, 3))
local_summary = _scoped(local_summary, "dmlc.cuts", (2, 3))


@lru_cache(maxsize=32)
def _mesh_summary_fn(mesh: Mesh, n_rows: int, n_summary: int, missing: bool,
                     weighted: bool):
    """The program behind :func:`mesh_summary`: one per mesh, row count
    and mode."""
    ndev = int(mesh.shape["data"])

    def per_chip(x, *weight):                  # x: this chip's rows [S, F]
        F = x.shape[1]
        x = jnp.pad(x, ((0, 0), (0, -F % ndev)))
        cols = jax.lax.all_to_all(x, "data", split_axis=1, concat_axis=0,
                                  tiled=True)  # [n_padded, F_padded / ndev]
        s = local_summary(cols[:n_rows], weight[0] if weighted else None,
                          n_summary, missing)
        return jax.lax.all_gather(s, "data", axis=0, tiled=True)[:F]

    return jax.jit(jax.named_scope("dmlc.cuts")(shard_map(
        per_chip, mesh=mesh,
        in_specs=(P("data", None),) + ((P(),) if weighted else ()),
        out_specs=P(), check_vma=False)))


def mesh_summary(x: jax.Array, weight: Optional[jax.Array], n_rows: int,
                 n_summary: int, missing: bool, mesh: Mesh) -> jax.Array:
    """:func:`local_summary` of the first ``n_rows`` rows of ``x``
    ``[n_padded, F]``, which lies in equal ROW shards over ``mesh``'s
    ``data`` axis (its only axis of more than one device; the pad rows
    are the tail) — computed BY FEATURE COLUMNS, every chip sorting
    ``F / ndev`` columns of ALL rows, and equal to :func:`local_summary`
    of ``x[:n_rows]`` on one device to the bit.

    A column's summary depends on no other column (all three bodies of
    :func:`local_summary` are per-column; ``weight`` is replicated), so
    the sort is split by columns and not by rows: a row shard's own
    quantiles scatter around the column's, and a merge of four such
    summaries is an approximate answer where this one is exact
    (PERF.md section 6, PR 52).  ONE ``shard_map`` program, device scope
    ``dmlc.cuts``: the columns padded with zeros to a multiple of the
    device count, ONE ``all_to_all`` that turns the ``[S, F]`` row
    shards into ``[n_padded, F / ndev]`` column shards in global row
    order, the pad rows sliced off (static: they are the tail, so they
    never reach the sort), the per-column body, and an ``all_gather`` of
    the ``[F / ndev, n_summary]`` results.  No chip ever holds the whole
    matrix: a row shard and a column shard, ``2 / ndev`` of it.

    Every chip ends with the whole ``[F, n_summary]`` summary: the
    result is replicated over the mesh (and committed to it, as
    everything computed from it is)."""
    fn = _mesh_summary_fn(mesh, n_rows, n_summary, missing,
                          weight is not None)
    return fn(x) if weight is None else fn(x, weight)


def merge_summaries(gathered: jax.Array, n_bins: int) -> jax.Array:
    """Merge ``[W, F, n_summary]`` worker summaries into ``[F, n_bins-1]``
    cut points (interior boundaries; bin b = count of cuts ≤ x).

    NaN summary points (a worker whose shard had no finite value for the
    feature — :func:`local_summary`'s sentinel rows) are excluded via
    ``nanquantile``, so a feature all-NaN on one shard but finite globally
    still gets finite cuts from the workers that saw it.  A feature with no
    finite point on ANY worker (callers reject this up front) degrades to a
    deterministic finite ramp rather than NaN cuts — NaN cuts would make
    ``searchsorted`` silently bin every finite value to 0.
    """
    W, F, S = gathered.shape
    merged = jnp.transpose(gathered, (1, 0, 2)).reshape(F, W * S)
    qs = jnp.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    cuts = jnp.nanquantile(merged, qs, axis=1).T                      # [F, n_bins-1]
    cuts = jnp.where(jnp.isnan(cuts),
                     jnp.arange(n_bins - 1, dtype=cuts.dtype)[None, :], cuts)
    # Strictly-increasing guard: s_i = max(c_i, s_{i-1} + eps_{i-1}) —
    # an atom-dominated feature (e.g. a sparse column densified to 0.0)
    # puts a RUN of quantile targets on one value, and a single-pass bump
    # against the unadjusted neighbor leaves runs ≥ 3 non-strict.  The
    # recurrence is a prefix max in disguise: with E = exclusive-prefix
    # sum of eps, s_i = E_i + cummax(c − E)_i, so duplicates fan upward
    # by one eps per position (rows still route identically — the bumped
    # copies sit between the atom and the next real value).
    eps = jnp.maximum(jnp.abs(cuts) * 1e-6, 1e-6)
    E = jnp.cumsum(eps, axis=1) - eps
    return E + jax.lax.cummax(cuts - E, axis=1)


_sketch_cuts = _scoped(merge_summaries, "dmlc.sketch.finalize", (1,))
merge_summaries = _scoped(merge_summaries, "dmlc.cuts", (1,))


def compute_cuts(
    x: np.ndarray,
    n_bins: int = 256,
    weight: Optional[np.ndarray] = None,
    n_summary: Optional[int] = None,
    allgather_fn=None,
    missing: bool = False,
    mesh: Optional[Mesh] = None,
    n_rows: Optional[int] = None,
) -> jax.Array:
    """End-to-end cut computation.

    ``mesh`` (more than one device, one process): ``x`` is a device
    array ``[n_padded, F]`` in row shards over the mesh, its first
    ``n_rows`` rows the data, and the summary is computed by feature
    columns across the chips (:func:`mesh_summary`) — the same cuts to
    the bit, replicated over the mesh (without ``allgather_fn``, which
    takes the summary through the host).

    ``allgather_fn(summary) -> [W, F, S]`` injects the distributed gather
    (e.g. ``collectives.allgather`` across processes, or an in-mesh
    all_gather); None means single worker.

    ``missing=True`` computes cuts over finite values only (NaN = missing;
    see :func:`local_summary`); callers reserve a bin for NaN separately
    (:func:`apply_bins` with ``missing=True``).
    """
    CHECK(n_bins >= 2, "need at least 2 bins")
    n_summary = n_summary or max(8 * n_bins, 64)
    weight = None if weight is None else jnp.asarray(weight)
    if mesh is None:
        summary = local_summary(jnp.asarray(x), weight, n_summary, missing)
    else:
        summary = mesh_summary(x, weight, n_rows, n_summary, missing, mesh)
    if allgather_fn is not None:
        gathered = jnp.asarray(allgather_fn(np.asarray(summary)))
    else:
        gathered = summary[None]
    return merge_summaries(gathered, n_bins)


def _weighted_collapse(stack: jax.Array, wts: jax.Array, n_out: int) -> jax.Array:
    """Merge ``[K, F, S]`` summaries with per-summary weights ``[K]`` into
    one ``[F, n_out]`` summary.

    Each summary point carries ``w_k / S`` mass; the merged multiset is
    re-quantiled on an even grid — the fixed-shape equivalent of the
    reference world's variable-size sketch merge (``GK/WQSummary.Merge``).
    """
    K, F, S = stack.shape
    pts = jnp.transpose(stack, (1, 0, 2)).reshape(F, K * S)            # [F, K·S]
    w = jnp.broadcast_to((wts / S)[:, None], (K, S)).reshape(K * S)    # [K·S]
    order = jnp.argsort(pts, axis=1)
    xs = jnp.take_along_axis(pts, order, axis=1)
    ws = jnp.broadcast_to(w[None, :], (F, K * S))
    ws = jnp.take_along_axis(ws, order, axis=1)
    cw = jnp.cumsum(ws, axis=1)
    total = cw[:, -1:]
    probs = (cw - 0.5 * ws) / total                                    # midpoint rule
    qs = jnp.linspace(0.0, 1.0, n_out)
    return jax.vmap(lambda xf, pf: jnp.interp(qs, pf, xf))(xs, probs)  # [F, n_out]


_ladder_merge = _scoped(_weighted_collapse, "dmlc.sketch.merge", (2,))
_final_collapse = _scoped(_weighted_collapse, "dmlc.sketch.finalize", (2,))


class SketchAccumulator:
    """Streaming quantile sketch with bounded memory (BASELINE config 3).

    The out-of-core path: pages of rows arrive one at a time (DiskRowIter /
    Parser over a 1TB input); each page contributes a fixed-size weighted
    summary, and summaries merge through a **C-ary ladder** (C =
    ``buffer_pages``): page summaries buffer at level 0; whenever a level
    holds C summaries they collapse into ONE summary at the next level.
    Any value therefore traverses at most ``⌈log_C P⌉`` merge stages — the
    rank-error bound grows *logarithmically* in the page count (see the
    module docstring's eps(S, P, C)), where a flat collapse-all buffer
    would compound error linearly in P.  Host memory stays
    ``O(C · log_C P · F · n_summary)``.

    ``finalize`` optionally allreduces (as an allgather+merge) across
    workers — the TPU-native replacement for the reference world's
    variable-size quantile-sketch allreduce (``tracker.py``-coordinated
    rabit ``SerializeReducer``).
    """

    def __init__(self, n_features: int, n_summary: int = 2048,
                 buffer_pages: int = 32):
        CHECK(buffer_pages >= 2, "need at least 2 buffered summaries")
        self._F = n_features
        self._S = n_summary
        self._cap = buffer_pages
        # merge ladder: _levels[ℓ] = list of ([F, S] summary, weight)
        self._levels: list = [[]]
        self.pages_seen = 0
        # Per-page summaries are jax ops on the default device; every
        # page pays an upload+dispatch round trip, so the sketch can be
        # pinned to the host CPU backend instead.
        backend = get_env("DMLC_TPU_SKETCH_BACKEND", "", str)
        self._device = (jax.local_devices(backend=backend)[0]
                        if backend else None)

    def add(self, x: np.ndarray, weight: Optional[np.ndarray] = None) -> None:
        """Absorb a page of rows ``[n, F]`` (``weight``: [n] or None)."""
        x = np.asarray(x, np.float32)
        CHECK(x.shape[1] == self._F, "feature-count mismatch")
        if x.shape[0] == 0:
            return
        wt = float(x.shape[0] if weight is None else np.sum(weight))
        if weight is not None and wt > 0 and np.all(weight == weight[0]):
            # one weight for every row (``iter_dense_slabs`` hands out
            # 1.0 where a page has none) is no weight: the quantile
            # function is the same, and the key-only sort computes it
            # where the weighted path carries a permutation through
            # the sort and gathers the page twice (a TPU gathers
            # element by element: PERF.md section 6, PR 51)
            weight = None
        with self._on_device():
            s = _page_summary(
                jnp.asarray(x),
                None if weight is None else jnp.asarray(weight),
                self._S)
            s = np.asarray(s)
        self.pages_seen += 1
        self._levels[0].append((s, wt))
        lvl = 0
        while len(self._levels[lvl]) >= self._cap:   # carry up the ladder
            merged = self._merge_group(self._levels[lvl])
            self._levels[lvl] = []
            if lvl + 1 == len(self._levels):
                self._levels.append([])
            self._levels[lvl + 1].append(merged)
            lvl += 1

    def _on_device(self):
        import contextlib

        return (jax.default_device(self._device) if self._device is not None
                else contextlib.nullcontext())

    def _merge_group(self, group: list) -> tuple:
        with self._on_device():
            stack = jnp.asarray(np.stack([s for s, _ in group]))
            wts = np.asarray([w for _, w in group], np.float32)
            merged = np.asarray(
                _ladder_merge(stack, jnp.asarray(wts), self._S))
        return merged, float(wts.sum())

    def summary(self) -> tuple:
        """Current ``([F, S] summary, total_weight)`` — the fixed-size
        message exchanged between workers.  Merges whatever sits on the
        ladder (one cross-level stage) without disturbing it."""
        pending = [sw for level in self._levels for sw in level]
        CHECK(pending, "no data added")
        if len(pending) == 1:
            return pending[0]
        return self._merge_group(pending)

    def finalize(self, n_bins: int, allgather_fn=None) -> jax.Array:
        """Merged cut points ``[F, n_bins-1]``.

        ``allgather_fn(arr) -> [W, ...]`` gathers across workers (e.g.
        ``collectives.allgather``); every worker computes identical cuts
        deterministically from the gathered summaries — no broadcast step.
        """
        local, wt = self.summary()
        if allgather_fn is not None:
            # allgather stacks rank contributions on a new leading axis
            gathered = np.asarray(allgather_fn(local))            # [W, F, S]
            wts = np.asarray(
                allgather_fn(np.asarray(wt, np.float32))).reshape(-1)  # [W]
        else:
            gathered = local[None]
            wts = np.asarray([wt], np.float32)
        merged = _final_collapse(
            jnp.asarray(gathered), jnp.asarray(wts), self._S)     # [F, S]
        return _sketch_cuts(merged[None], n_bins)


#: the cut axis is counted as [K, _CUT_FOLD]: XLA:CPU rewrites a reduce
#: over more than 32 elements into a reduce-window tree that it cannot
#: fuse with the compare, and then holds the whole [F, C, n] int32 (5.6
#: GB for 200k × 28 × 255); two axes of ≤ 32 it fuses, up to 1024 cuts.
#: The TPU compiler gives the same one fusion either way.
_CUT_FOLD = 32


@partial(jax.jit, static_argnames=("miss_bin", "cat"))
@jax.named_scope("dmlc.bin")
def apply_bins_t(x: jax.Array, cuts: jax.Array,
                 miss_bin: Optional[int] = None,
                 cat: Optional[tuple] = None) -> jax.Array:
    """Digitize ``x`` [n, F] by per-feature ``cuts`` [F, n_bins-1] →
    integer bins FEATURE-MAJOR [F, n]: the layout the round program
    reads, and the one the count is cheapest in.

    ``bin = #{c : not (v < cuts[f, c])}``: one compare of every value
    against every cut of its feature and a sum over the cut axis, rows on
    the lanes — a single loop fusion with no ``[F, C, n]`` buffer (the
    compiler streams the cut axis; 2M × 28 against 255 cuts holds 244 MiB
    of temporaries).  A binary search is O(log C) but each of its steps
    is a gather, which a TPU does element by element: on a v5e it took
    5.3 s for that slab, this count 22 ms (8 ms at 64 bins, 72 ms at
    1024: PERF.md §6, PR 28).  ``not (v < c)`` rather than ``c <= v``
    makes the count equal ``searchsorted(cuts[f], v, side="right")`` for
    EVERY float32: NaN compares false, so it counts every cut and lands
    in ``n_cuts``.

    TIES: a value EQUAL to a cut counts that cut — it falls in the bin to
    the cut's RIGHT (``bin`` = number of cuts ``<= v``), and a tree's
    ``bin > thr`` sends ``v >= cuts[f, thr]`` right.  Integer-valued and
    indicator columns sit on their cuts in every row (a two-valued
    column's cuts repeat one value, bumped apart by ``max(|c|, 1) * 1e-6``
    steps: all of them count for the larger value, the first alone for
    the smaller); the benchmark's reference bins by the same rule
    (``reference.bin_rows``: ``searchsorted(..., side="right")``) and the
    multiclass cell counts the mismatches on such columns.

    ``miss_bin`` (missing mode) sends NaN to that reserved bin instead —
    the caller reserves its top bin; without it NaN would alias the top
    VALUE bin and score garbage.

    ``cat`` (a bool per feature; None or all False: no such column, and
    the program is the one it always was) marks the CATEGORICAL columns,
    whose row of ``cuts`` is a category→bin table (:func:`cat_tables`):
    the bin is the position of the value in that row, and ``n_cuts`` —
    the last bin, "other" — for a value the row lacks (a level rarer than
    the named ones, or one unseen when the table was made).  The same
    compare of every value with every entry, summed over the entry axis;
    device scope ``dmlc.bin.cat``.

    dtype: uint8 when bins fit (largest bin < 256: ``n_bins`` ≤ 256, the
    XGBoost max_bin default) — the bin matrix is the largest resident
    training array and the narrow dtype quarters its HBM footprint under
    TPU tiling; int32 otherwise.
    """
    F, C = cuts.shape
    # pad to whole folds with -inf, which EVERY value counts (NaN and
    # -inf too): the pads come off the sum again, no mask needed
    n_pad = -C % _CUT_FOLD
    folded = jnp.pad(cuts, ((0, 0), (n_pad, 0)), constant_values=-jnp.inf
                     ).reshape(F, -1, _CUT_FOLD)
    xt = x.T
    out = jnp.sum(~(xt[:, None, None, :] < folded[:, :, :, None]),
                  axis=(1, 2), dtype=jnp.int32) - n_pad
    top = C
    if miss_bin is not None:
        out = jnp.where(jnp.isnan(xt), miss_bin, out)
        top = miss_bin
    if cat is not None and any(cat):
        with jax.named_scope("dmlc.bin.cat"):
            # position + 1 of the one entry that equals the value, 0 where
            # none does; a pad (-1, and the fold's) is NaN: equal to nothing
            table = jnp.pad(jnp.where(cuts >= 0, cuts, jnp.nan),
                            ((0, 0), (n_pad, 0)), constant_values=jnp.nan
                            ).reshape(F, -1, _CUT_FOLD)
            place = jnp.arange(1 - n_pad, C + 1, dtype=jnp.int32
                               ).reshape(1, -1, _CUT_FOLD, 1)
            hit = jnp.sum(jnp.where(
                xt[:, None, None, :] == table[:, :, :, None], place, 0),
                axis=(1, 2), dtype=jnp.int32)
            out = jnp.where(jnp.asarray(cat)[:, None],
                            jnp.where(hit > 0, hit - 1, C), out)
    return out.astype(jnp.uint8 if top < 256 else jnp.int32)


@partial(jax.jit, static_argnames=("cat",))
@jax.named_scope("dmlc.bin")
def apply_bins(x: jax.Array, cuts: jax.Array,
               cat: Optional[tuple] = None) -> jax.Array:
    """:func:`apply_bins_t` row-major: ``x`` [n, F] → bins [n, F]
    (bin = #cuts ≤ value, so bins ∈ [0, n_bins-1]; NaN → ``n_bins-1``;
    a ``cat`` column by its table).
    """
    return apply_bins_t(x, cuts, cat=cat).T


@partial(jax.jit, static_argnames=("miss_bin",))
@jax.named_scope("dmlc.bin")
def apply_bins_missing(x: jax.Array, cuts: jax.Array,
                       miss_bin: int) -> jax.Array:
    """:func:`apply_bins` with a reserved NaN bin: finite values digitize
    into ``[0, n_cuts]`` as usual and NaN maps to ``miss_bin``.
    """
    return apply_bins_t(x, cuts, miss_bin=miss_bin).T


def _column_counts(x: jax.Array) -> tuple:
    """Per column of ``x`` [n, F] the number of NaN and the number of
    finite values, both int32 (a column has fewer than 2**31 rows)."""
    return (jnp.sum(jnp.isnan(x), axis=0, dtype=jnp.int32),
            jnp.sum(jnp.isfinite(x), axis=0, dtype=jnp.int32))


@jax.jit
@jax.named_scope("dmlc.cuts.nan_scan")
def nan_scan(x: jax.Array) -> tuple:
    """What the choice of the missing mode needs to know of ``x`` [n, F],
    from ONE pass over it where it lies: ``(nan_count, finite_any)`` —
    per column the number of NaN (int32: a column has fewer than 2**31
    rows; a caller that wants the matrix's total adds the F counts up in
    Python ints) and whether it holds a finite value (bool).  NumPy's
    meanings: +-inf is not NaN and not finite, so a column of inf and
    NaN alone has no finite value.

    Both facts are int32 column sums, so that the TPU compiler makes
    them ONE fusion that reads the matrix once, at HBM's rate (5.0 ms at
    24M x 28, 7.0 at 1,183,747 x 968; a sum beside an ``any`` is two
    fusions, two reads, twice the time: PERF.md section 6, PR 50), and
    writes nothing of the matrix's size.  A device scope of its own
    (``dmlc.cuts.nan_scan``): ``dmlc.cuts`` stays the summary and the
    merge."""
    nan_count, finite_count = _column_counts(x)
    return nan_count, finite_count > 0


@lru_cache(maxsize=32)
def _mesh_nan_scan_fn(mesh: Mesh, n_pad: int):
    def per_chip(x):
        nan_count, finite_count = jax.lax.psum(_column_counts(x), "data")
        return nan_count, finite_count - n_pad > 0

    return jax.jit(jax.named_scope("dmlc.cuts.nan_scan")(shard_map(
        per_chip, mesh=mesh, in_specs=(P("data", None),), out_specs=P(),
        check_vma=False)))


def mesh_nan_scan(x: jax.Array, n_rows: int, mesh: Mesh) -> tuple:
    """:func:`nan_scan` of the first ``n_rows`` rows of ``x``
    ``[n_padded, F]`` lying in row shards over ``mesh`` (as
    :func:`mesh_summary` takes it): every chip scans its own shard, ONE
    ``psum`` adds the counts up.  The pad rows are ZEROS — never NaN,
    always finite — so they come off the finite count and a column
    whose data holds no finite value still reads so."""
    return _mesh_nan_scan_fn(mesh, x.shape[0] - n_rows)(x)


# -- categorical columns: codes, counts, category→bin tables ----------------

#: codes a categorical column may hold: whole numbers ``0 .. CAT_MAX_CODE
#: - 1``.  The counts are a compare of every row with every code (one
#: fusion, no ``[n, K]`` buffer: the binning's own form), so the bound is
#: on work, not on memory; a column of more levels than this is an id,
#: not a category
CAT_MAX_CODE = 1 << 16


def _cat_index(cat: tuple) -> np.ndarray:
    return np.flatnonzero(np.asarray(cat, bool))


def _on_mesh(per_chip, mesh: Optional[Mesh], scope: str):
    """``per_chip(x)`` as a program under device scope ``scope``: on the
    matrix as it lies on one device, or on every chip's row shard of a
    mesh (``per_chip`` then reduces over ``data`` itself)."""
    if mesh is not None:
        per_chip = shard_map(per_chip, mesh=mesh, in_specs=(P("data", None),),
                             out_specs=P(), check_vma=False)
    return jax.jit(jax.named_scope(scope)(per_chip))


@lru_cache(maxsize=32)
def _cat_scan_fn(cat: tuple, mesh: Optional[Mesh]):
    idx = _cat_index(cat)

    def per_chip(x):
        # every column, as nan_scan reads them (ONE fusion at HBM's rate;
        # a gather of the categorical ones goes an element at a time),
        # then the [F] results cut to the categorical columns.  NaN is
        # not its own floor: it counts as bad too
        bad = jnp.sum((x != jnp.floor(x)) | (x < 0), axis=0,
                      dtype=jnp.int32)[idx]
        top = jnp.max(x, axis=0, initial=-jnp.inf)[idx]
        if mesh is not None:
            bad, top = jax.lax.psum(bad, "data"), jax.lax.pmax(top, "data")
        return bad, top

    return _on_mesh(per_chip, mesh, "dmlc.cats")


def cat_scan(x: jax.Array, cat: tuple, mesh: Optional[Mesh] = None) -> tuple:
    """What a table's categorical columns have to be told before anything
    is made of them, from ONE pass over ``x`` [n, F] where it lies (with
    ``mesh``: in row shards, one ``psum``): per ``cat`` column the number
    of values that are no code — negative, fractional or NaN — and the
    largest value.  ``(bad [Fc] int32, top [Fc] float32)``."""
    return _cat_scan_fn(tuple(map(bool, cat)), mesh)(x)


@lru_cache(maxsize=32)
def _cat_tables_fn(cat: tuple, n_codes: int, n_named: int, n_pad: int,
                   mesh: Optional[Mesh]):
    idx = _cat_index(cat)
    folds = -(-n_codes // _CUT_FOLD)

    def per_chip(x):
        # (column slices side by side, not a gather)
        codes = jnp.stack([x[:, f] for f in idx], axis=0)      # [Fc, n]
        # counts[f, k] = rows of column f that hold code k: one compare
        # of every row with every code, summed over the rows (the code
        # axis folded as the cut axis is, see _CUT_FOLD)
        code = jnp.arange(folds * _CUT_FOLD, dtype=x.dtype
                          ).reshape(1, folds, _CUT_FOLD, 1)
        counts = jnp.sum(codes[:, None, None, :] == code, axis=3,
                         dtype=jnp.int32).reshape(len(idx), -1)[:, :n_codes]
        if mesh is not None:
            counts = jax.lax.psum(counts, "data")
        # a mesh's pad rows are zeros at the tail: never code 0's rows
        counts = counts.at[:, 0].add(-n_pad)
        # falling count, ties to the lower code (the sort is stable)
        order = jnp.argsort(-counts, axis=1, stable=True)
        seen = jnp.take_along_axis(counts, order, axis=1) > 0
        named = jnp.where(seen, order, -1)[:, :n_named]
        named = jnp.pad(named, ((0, 0), (0, n_named - named.shape[1])),
                        constant_values=-1)
        return named.astype(x.dtype)

    return _on_mesh(per_chip, mesh, "dmlc.cats")


def cat_tables(x: jax.Array, cat: tuple, n_codes: int, n_bins: int,
               n_rows: Optional[int] = None,
               mesh: Optional[Mesh] = None) -> jax.Array:
    """The category→bin tables of the ``cat`` columns of ``x`` [n, F]
    (codes ``0 .. n_codes-1``, as :func:`cat_scan` found them), LightGBM's
    ``BinMapper`` rule: the codes by FALLING COUNT over all rows, ties to
    the lower code, take bins ``0, 1, 2, ...``; at most ``n_bins - 1``
    are named and every rarer level shares the last bin, ``n_bins - 1``
    ("other"), with every code unseen here.

    Returns the tables ``[Fc, n_bins-1]``: the code of bin ``k`` at ``k``
    and -1 past the named bins — each a row of the cut matrix, see
    :func:`apply_bins_t`.  With ``mesh``, ``x``
    lies there in row shards, its first ``n_rows`` rows the data and the
    rest zeros: the chips count their own rows and ONE ``psum`` adds the
    counts up before the tables are made, alike on every chip."""
    CHECK(0 < n_codes <= CAT_MAX_CODE,
          f"a categorical column holds codes 0..{CAT_MAX_CODE - 1}, "
          f"got {n_codes - 1}")
    n_pad = 0 if n_rows is None else x.shape[0] - n_rows
    return _cat_tables_fn(tuple(map(bool, cat)), n_codes, n_bins - 1, n_pad,
                          mesh)(x)


def cat_bins_used(cuts: np.ndarray, cat: tuple) -> tuple:
    """Per feature the bins ``0..c-1`` its training rows can hold, read
    off the cut matrix (host): a categorical column's named bins, and
    ALL ``n_bins`` where every one of them is taken (so that "other" is
    counted whether or not a level was left over); 0 for a numeric
    column.  What the split scan is told (``_make_best_split``'s
    ``cat_bins``)."""
    cuts = np.asarray(cuts)
    named = (cuts >= 0).sum(axis=1)
    return tuple(
        0 if not is_cat else int(c) if c < cuts.shape[1] else int(c) + 1
        for is_cat, c in zip(cat, named))
