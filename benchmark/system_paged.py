"""The seam to the system's DATA PLANE: the second module of the
benchmark that imports ``dmlc_core_tpu`` beside ``system.py`` (whose
docstring says it is the only one: it cannot be edited by the PR that
adds this file).  It calls what a user who trains from a LibSVM file
with ``#cache`` calls — ``DiskRowIter`` pages on local disk,
``iter_dense_slabs`` over them, ``HistGBT.make_device_data_iter`` — with
the configuration's sizes and nothing else: no ``cuts=``, no ``DMLC_*``
variable.  The model itself comes from ``system.new_model``.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, Iterable, Tuple

import numpy as np

from benchmark import checks_paged, datagen_onehot, system


class CsrBlocks:
    """Seeded CSR blocks as the parser ``DiskRowIter`` builds its cache
    from: an iterable of ``RowBlock`` with a ``close``."""

    def __init__(self, blocks: Iterable[Tuple[np.ndarray, ...]]):
        self._blocks = blocks

    def __iter__(self):
        from dmlc_core_tpu.data.row_block import RowBlock

        for offset, index, value, y in self._blocks:
            yield RowBlock(offset=offset, label=y, index=index, value=value)

    def close(self) -> None:
        pass


class Pages:
    """A ``DiskRowIter`` page cache and a count of the pages it has
    handed out since :meth:`reset` — counted here, at the seam, so that
    the check does not lean on the program's own counters."""

    def __init__(self, row_iter, cache_path: str):
        self.row_iter, self.cache_path = row_iter, cache_path
        self.replayed = 0
        self.count = sum(1 for _ in row_iter)     # one replay says

    def reset(self) -> None:
        self.replayed = 0

    def __iter__(self):
        for block in self.row_iter:
            self.replayed += 1
            yield block

    def drop(self) -> None:
        self.row_iter.close()
        if os.path.exists(self.cache_path):
            os.remove(self.cache_path)


def build_pages(blocks: Iterable[Tuple[np.ndarray, ...]], cache_path: str
                ) -> Pages:
    """The ``DiskRowIter`` page cache of CSR blocks ``(offset, index,
    value, y)`` at ``cache_path``, pages of the library's default size."""
    from dmlc_core_tpu.data.iter import DiskRowIter

    os.makedirs(os.path.dirname(cache_path), exist_ok=True)
    return Pages(DiskRowIter(CsrBlocks(blocks), cache_path), cache_path)


def stage_pages(ctx):
    """The seeded CSR blocks and their page cache on local disk."""
    blocks = list(datagen_onehot.allstate_like(
        int(ctx.config["rows"]), ctx.seed, stream=0,
        levels=datagen_onehot.levels_of(ctx.config)))
    path = os.path.join(ctx.root, "benchmark", ".out", "pages",
                        f"{ctx.workload}.{ctx.seed}.cache")
    t0 = time.perf_counter()
    pages = build_pages(blocks, path)
    ctx.say(f"[bench] {pages.count} pages of "
            f"{sum(len(b[1]) for b in blocks)} entries built and replayed "
            f"once in {time.perf_counter() - t0:.3f} s")
    return blocks, pages


def heldout_rows(ctx, rows: int) -> Tuple[np.ndarray, np.ndarray]:
    """``rows`` held-out rows (stream 1 of the same book), densified
    float64, and their labels."""
    blocks = list(datagen_onehot.allstate_like(
        rows, ctx.seed, stream=1,
        levels=datagen_onehot.levels_of(ctx.config)))
    return (checks_paged.dense_rows(blocks, 0, rows,
                                    int(ctx.config["features"])),
            np.concatenate([b[3] for b in blocks]))


def ingest_paged(model, pages: Pages, features: int, slab_rows: int
                 ) -> Dict[str, Any]:
    """``make_device_data_iter`` over the pages as a user calls it — both
    passes, the sketch and the binning — waited for: the handle counts
    as made only when every array of it is ready."""
    import jax

    from dmlc_core_tpu.data.iter import iter_dense_slabs

    pages.reset()
    handle = model.make_device_data_iter(
        lambda: iter_dense_slabs(pages, int(features), int(slab_rows)))
    jax.block_until_ready([v for v in handle.values()
                           if isinstance(v, jax.Array)])
    return handle


def fetch_feature_rows(arr, n: int, block: int = 512) -> np.ndarray:
    """The first ``n`` columns of a device matrix ``[F, n_padded]`` as a
    host array, fetched ``block`` feature rows at a time (one program
    for every offset; the last block re-reads a few rows), so that no
    transfer nears 2^32 bytes."""
    import jax

    F = arr.shape[0]
    block = min(block, F)
    out = np.empty((F, n), np.asarray(arr[:1, :1]).dtype)
    for lo in list(range(0, F - block, block)) + [F - block]:
        out[lo:lo + block] = np.asarray(
            jax.lax.dynamic_slice_in_dim(arr, lo, block, axis=0))[:, :n]
    return out


def last_ingest_record():
    """The program's newest ``dmlc.ingest`` record (``profiler.op_log``),
    None on a program that keeps none: its counts say what the iterator
    path moved (``pages``, ``slabs``, ``nnz``, ``dense_bytes``)."""
    from dmlc_core_tpu.utils import profiler

    if not hasattr(profiler, "op_log"):
        return None
    recs = [r for r in profiler.op_log() if r["name"] == "dmlc.ingest"]
    return recs[-1] if recs else None


def sketch_cuts(pages: Pages, features: int, slab_rows: int, n_bins: int,
                n_summary: int, slabs: int = 0) -> np.ndarray:
    """Cuts of the library's streaming sketch over the first ``slabs``
    slabs of the pages (0 = all) at ``n_summary`` points: what the
    CONTROLS put in the program's place (a thinner sketch, a sketch of
    the first slab alone); ``make_device_data_iter`` builds its own."""
    from dmlc_core_tpu.data.iter import iter_dense_slabs
    from dmlc_core_tpu.ops.quantile import SketchAccumulator

    sketch = SketchAccumulator(int(features), n_summary=int(n_summary))
    for k, (X, _y, _w) in enumerate(
            iter_dense_slabs(pages, int(features), int(slab_rows))):
        if slabs and k >= slabs:
            break
        sketch.add(np.array(X, dtype=np.float32))
    return np.asarray(sketch.finalize(int(n_bins)))


def compile_round_program(model, rows: int, features: int) -> None:
    """Compile the model's round program for a table of this shape NOW
    and wait for it (``HistGBT.start_warmup``, what a user calls to
    overlap the compile with loading): a program the chip's compiler
    refuses raises here, before a row is drawn.  The ingest's own
    background compile then reads the cache."""
    model.start_warmup(int(rows), int(features))
    system.join_background(model)
