"""The matrix the cuts are computed from crosses to the device in pieces
on EVERY path of ``_stage_device_data`` (PR 43): one chip and one slab
(where it is also the slab that is binned), one chip and several slabs —
through ``models/histgbt.py::_put_matrix`` — and a mesh, where it goes
as one row shard a chip (``HistGBT._put_row_shards``) that is sorted by columns and
binned where it lies (PR 52).  Past ``_PUT_CLIFF_BYTES`` (on a mesh:
past a slab's rows too) a put goes in row pieces of at most
``_PUT_PIECE_BYTES``; below the mark it is ONE put (a chip) — and either
way the cuts and the binned matrix are the same bytes.

On the chip the mark is 2**32 bytes (one transfer of 4.48 GB held four
chips 24.9 s, PERF.md section 5); here both constants are patched small.
"""

import numpy as np
import pytest

from dmlc_core_tpu.base import compile_cache
from dmlc_core_tpu.models import HistGBT
from dmlc_core_tpu.models import histgbt as G
from dmlc_core_tpu.parallel.mesh import local_mesh
from dmlc_core_tpu.utils import profiler

ROWS, FEATURES = 1000, 7
CLIFF = 1000                         # bytes

# path -> (chips of the mesh; DMLC_INGEST_CHUNK_ROWS or None for the
# default: one slab; _PUT_PIECE_BYTES: the 28,000 B of one chip go in
# four pieces, a mesh chip's 7,000 B in three).  The one slab and the
# mesh's shards are put once and no stream puts them again.
PATHS = {
    "one_chip_multi_slab": (1, "256", 9000),
    "mesh4_sharded_ingest": (4, "256", 3000),
    "one_chip_one_slab": (1, None, 9000),
}


def _matrix(holes: bool):
    rng = np.random.default_rng(43)
    X = rng.normal(size=(ROWS, FEATURES)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] > 0).astype(np.float32)
    if holes:
        X[rng.random(X.shape) < 0.3] = np.nan
    return X, y


def _ingest(ndev: int, X, y):
    """One ``make_device_data`` on a fresh model: the model, the handle,
    the operation's record and the puts that lie inside
    ``dmlc.ingest.cuts`` (from the host tracer's events of that ``op``),
    as their bytes."""
    m = HistGBT(n_trees=1, max_depth=2, n_bins=16, mesh=local_mesh(ndev))
    before = len(profiler.op_log())
    h = m.make_device_data(X, y)
    m._pending_warmup.join()
    (rec,) = [r for r in profiler.op_log()[before:]
              if r["name"] == "dmlc.ingest"]
    events = [e for e in profiler.global_tracer().events()
              if e.get("args", {}).get("op") == rec["op"]]
    (cuts,) = [e for e in events if e["name"] == "dmlc.ingest.cuts"]
    cut_puts = [e["args"]["bytes"] for e in events
                if e["name"] == "dmlc.ingest.put"
                and cuts["ts"] <= e["ts"]
                and e["ts"] + e["dur"] <= cuts["ts"] + cuts["dur"]]
    return m, h, rec, cut_puts


@pytest.mark.parametrize("holes", [False, True], ids=["dense", "holes"])
@pytest.mark.parametrize("path", list(PATHS))
def test_the_cut_matrix_goes_in_pieces_on_every_path(path, holes,
                                                     monkeypatch):
    ndev, chunk_rows, piece = PATHS[path]
    streams_slabs = chunk_rows is not None and ndev == 1
    if chunk_rows is not None:
        monkeypatch.setenv("DMLC_INGEST_CHUNK_ROWS", chunk_rows)
    monkeypatch.setattr(profiler, "_TRACING", True)
    X, y = _matrix(holes)
    pieces = ndev * -(-X.nbytes // ndev // piece)

    # below the mark: exactly ONE put (a chip) for the cut matrix, a
    # span where ``jnp.asarray`` used to leave none
    whole, h_whole, rec_whole, cut_puts = _ingest(ndev, X, y)
    assert cut_puts == [X.nbytes // ndev] * ndev
    n_whole, _s, _l, bytes_whole = rec_whole["children"]["dmlc.ingest.put"]
    assert bytes_whole == X.nbytes * (2 if streams_slabs else 1)
    assert (n_whole > ndev) == streams_slabs
    # a stream of slabs, and a mesh's shards, pace their puts
    assert ("dmlc.ingest.put_wait" in rec_whole["children"]) == (
        path != "one_chip_one_slab")

    # past it: row pieces whose bytes sum to the matrix, counted by the
    # record beside whatever the slab stream puts
    monkeypatch.setattr(G, "_PUT_CLIFF_BYTES", CLIFF)
    monkeypatch.setattr(G, "_PUT_PIECE_BYTES", piece)
    pieced, h_pieced, rec_pieced, cut_puts = _ingest(ndev, X, y)
    assert len(cut_puts) == pieces and sum(cut_puts) == X.nbytes
    assert max(cut_puts) <= piece
    n_pieced, _s, _l, bytes_pieced = rec_pieced["children"]["dmlc.ingest.put"]
    assert n_pieced - n_whole == pieces - ndev
    assert bytes_pieced == bytes_whole

    # the same cuts and the same binned matrix, to the byte
    assert pieced._missing is whole._missing is holes
    assert np.array_equal(np.asarray(pieced.cuts), np.asarray(whole.cuts))
    assert h_pieced["bins_t"].sharding == h_whole["bins_t"].sharding
    assert np.array_equal(np.asarray(h_pieced["bins_t"]),
                          np.asarray(h_whole["bins_t"]))

    # a second ingest in pieces compiles nothing more
    st = compile_cache.stats()
    _ingest(ndev, X, y)
    after = compile_cache.stats()
    assert (after["hits"], after["misses"]) == (st["hits"], st["misses"])


def test_put_matrix_without_a_sharding_is_jnp_asarrays_placement(
        monkeypatch):
    """``_put_matrix(X, None)`` lands where ``jnp.asarray(X)`` does —
    whole, on the default device, uncommitted — in one piece or in
    several."""
    import jax.numpy as jnp

    X, _y = _matrix(False)
    want = jnp.asarray(X)
    for patched in (False, True):
        if patched:
            monkeypatch.setattr(G, "_PUT_CLIFF_BYTES", CLIFF)
            monkeypatch.setattr(G, "_PUT_PIECE_BYTES", 9000)
        got = G._put_matrix(X, None)
        assert got.devices() == want.devices()
        assert got.committed is False and want.committed is False
        assert got.dtype == want.dtype
        assert np.array_equal(np.asarray(got), X)
