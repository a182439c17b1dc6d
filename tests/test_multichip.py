"""Multi-chip data-parallel HistGBT: sharded ingest + oracle parity.

The ISSUE 7 contracts, pinned on the 8-virtual-device CPU mesh the
whole suite runs under (conftest):

* row-range math tiles exactly for ANY odd size (the input_split
  contract lifted to rows, plus the slab→shard tail math);
* sharded per-chip ingest is byte-identical to the global staging path;
* with the deterministic histogram reduction (``DMLC_HIST_BLOCKS``) an
  N-chip fit serializes byte-identically to the 1-chip oracle;
* out-of-core streamed ingest (``make_device_data_iter``, tiny chunk
  slabs, DiskRowIter-backed) matches the in-core ensemble bit-exactly;
* the histogram-psum traffic metric matches the analytic model;
* (ISSUE 52) a first ingest on a mesh sorts the cut columns BY FEATURES
  over the chips — the cuts and the bin matrix are a one-device model's
  to the byte, and no chip is ever given the whole matrix.
"""

import os
import sys

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dmlc_core_tpu.data.iter import (RowBlockIter, iter_dense_slabs,  # noqa: E402
                                     slab_shard_slices)
from dmlc_core_tpu.base.logging import Error  # noqa: E402
from dmlc_core_tpu.models import HistGBT  # noqa: E402
from dmlc_core_tpu.models import histgbt as G  # noqa: E402
from dmlc_core_tpu.models.histgbt import _tree_fold  # noqa: E402
from dmlc_core_tpu.ops import quantile as Q  # noqa: E402
from dmlc_core_tpu.ops.histogram import hist_psum_bytes_per_round  # noqa: E402
from dmlc_core_tpu.ops.quantile import compute_cuts  # noqa: E402
from dmlc_core_tpu.parallel.mesh import (device_count, local_mesh,  # noqa: E402
                                         row_shard_layout,
                                         shard_row_ranges)
from dmlc_core_tpu.utils import profiler  # noqa: E402

KW = dict(n_trees=3, max_depth=3, n_bins=16, learning_rate=0.3)


def _make_xy(n, F=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + 0.5 * X[:, 2] > 0).astype(np.float32)
    return X, y


def _trees_equal(a, b):
    return (len(a) == len(b)
            and all(np.array_equal(ta[k], tb[k])
                    for ta, tb in zip(a, b) for k in ta))


class TestRowRangeMath:
    def test_shard_row_ranges_tile_exactly(self):
        # property sweep over odd sizes: disjoint, ordered, union exact
        for n in (0, 1, 2, 7, 8, 9, 63, 64, 65, 1000, 1013, 4097):
            for k in (1, 2, 3, 5, 7, 8, 16, 1001):
                ranges = shard_row_ranges(n, k)
                assert len(ranges) == k
                pos = 0
                for lo, hi in ranges:
                    assert lo == pos and hi >= lo
                    pos = hi
                assert pos == n
                # remainder spreads: no part exceeds ceil(n/k)
                assert max(hi - lo for lo, hi in ranges) <= -(-n // k) \
                    if n else True

    def test_slab_shard_slices_cover_every_row_once(self):
        # simulate the sharded ingest scatter over odd chunk/tail combos
        rng = np.random.default_rng(3)
        for n, chunk, ndev in [(1013, 96, 8), (64, 64, 8), (100, 7, 4),
                               (8, 3, 8), (4096, 1000, 8), (17, 100, 2)]:
            n_padded, S = row_shard_layout(n, local_mesh(ndev))
            seen = np.zeros(n, np.int32)
            dest = np.full(n, -1, np.int64)
            for lo in range(0, n, chunk):
                length = min(chunk, n - lo)
                pieces = slab_shard_slices(lo, length, S)
                covered = 0
                for k, s_lo, s_hi, dst in pieces:
                    assert 0 <= k < ndev
                    assert 0 <= dst and dst + (s_hi - s_lo) <= S
                    seen[lo + s_lo:lo + s_hi] += 1
                    dest[lo + s_lo:lo + s_hi] = np.arange(
                        k * S + dst, k * S + dst + (s_hi - s_lo))
                    covered += s_hi - s_lo
                assert covered == length
            assert (seen == 1).all(), "a row was dropped or duplicated"
            # global placement is the identity: row i lands at offset i
            assert np.array_equal(dest, np.arange(n))

    def test_row_shard_layout_padding(self):
        mesh = local_mesh(8)
        n_padded, S = row_shard_layout(1013, mesh)
        assert n_padded % 8 == 0 and n_padded >= 1013 and S == n_padded // 8
        # coarser pad multiple (deterministic blocks): lcm honored
        n_padded2, S2 = row_shard_layout(1013, mesh, pad_multiple=32)
        assert n_padded2 % 32 == 0 and S2 * 8 == n_padded2

    def test_tree_fold_composition(self):
        # the fold over C leaves must equal per-shard folds of aligned
        # sub-ranges folded again — the property 1-vs-N parity rests on
        rng = np.random.default_rng(5)
        parts = [rng.normal(size=(4, 3)).astype(np.float32)
                 for _ in range(16)]
        full = _tree_fold(list(parts))
        for nshard in (2, 4, 8, 16):
            per = len(parts) // nshard
            partials = [_tree_fold(parts[i * per:(i + 1) * per])
                        for i in range(nshard)]
            again = _tree_fold(partials)
            assert np.array_equal(full, again), f"nshard={nshard}"


class TestInputSplitOddSizes:
    def test_recordio_parts_tile_exactly(self, tmp_path):
        # property-style: odd record counts/sizes across several files;
        # for every nparts the union over parts is the full record set,
        # no overlap, order preserved within parts
        from dmlc_core_tpu.io.input_split import InputSplit
        from dmlc_core_tpu.io.recordio import encode_records

        rng = np.random.default_rng(11)
        records = []
        for fi, count in enumerate((17, 1, 23, 8)):
            recs = [bytes(rng.integers(0, 256, size=int(sz), dtype=np.uint8))
                    for sz in rng.integers(1, 200, size=count)]
            (tmp_path / f"part-{fi}.rec").write_bytes(encode_records(recs))
            records.extend(recs)
        uri = str(tmp_path / "part-*.rec")
        # glob isn't a thing here: list files explicitly via ';'
        uri = ";".join(str(tmp_path / f"part-{fi}.rec") for fi in range(4))
        for nparts in (1, 2, 3, 5, 8, 11):
            got = []
            for part in range(nparts):
                with InputSplit.create(uri, part, nparts, "recordio",
                                       threaded=False) as sp:
                    got.extend(iter(sp))
            assert got == records, f"nparts={nparts}"


class TestShardedIngestParity:
    def test_sharded_vs_global_staging_bit_identical(self, monkeypatch):
        X, y = _make_xy(1013)
        cuts = compute_cuts(X, KW["n_bins"])
        mesh = local_mesh(8)
        # the global-put fallback of a mesh that spans processes
        with monkeypatch.context() as mp:
            mp.setattr(HistGBT, "_sharded_ingest_ok", lambda self: False)
            m_gl = HistGBT(mesh=mesh, **KW)
            dd_gl = m_gl.make_device_data(X, y, cuts=cuts)
        m_sh = HistGBT(mesh=mesh, **KW)
        dd_sh = m_sh.make_device_data(X, y, cuts=cuts)
        assert np.array_equal(np.asarray(dd_gl["bins_t"]),
                              np.asarray(dd_sh["bins_t"]))
        assert np.array_equal(np.asarray(dd_gl["y_d"]),
                              np.asarray(dd_sh["y_d"]))
        assert np.array_equal(np.asarray(dd_gl["w_d"]),
                              np.asarray(dd_sh["w_d"]))
        m_gl.fit_device(dd_gl)
        m_sh.fit_device(dd_sh)
        assert _trees_equal(m_gl.trees, m_sh.trees)

    def test_sharded_ingest_host_bin_route(self, monkeypatch):
        # DMLC_TPU_BIN_BACKEND=cpu (the bench staging mode) through the
        # per-chip placement must match the device-bin route exactly
        X, y = _make_xy(519, seed=2)
        cuts = compute_cuts(X, KW["n_bins"])
        m_dev = HistGBT(mesh=local_mesh(8), **KW)
        dd_dev = m_dev.make_device_data(X, y, cuts=cuts)
        monkeypatch.setenv("DMLC_TPU_BIN_BACKEND", "cpu")
        m_cpu = HistGBT(mesh=local_mesh(8), **KW)
        dd_cpu = m_cpu.make_device_data(X, y, cuts=cuts)
        assert np.array_equal(np.asarray(dd_dev["bins_t"]),
                              np.asarray(dd_cpu["bins_t"]))

    def test_chunked_sharded_ingest_matches_single_slab(self, monkeypatch):
        # nrows % (chips * chunk) != 0: the streamed tail must place
        # identically to a one-slab ingest
        X, y = _make_xy(1111, seed=4)
        cuts = compute_cuts(X, KW["n_bins"])
        m_one = HistGBT(mesh=local_mesh(8), **KW)
        dd_one = m_one.make_device_data(X, y, cuts=cuts)
        monkeypatch.setenv("DMLC_INGEST_CHUNK_ROWS", "96")
        m_chk = HistGBT(mesh=local_mesh(8), **KW)
        dd_chk = m_chk.make_device_data(X, y, cuts=cuts)
        assert np.array_equal(np.asarray(dd_one["bins_t"]),
                              np.asarray(dd_chk["bins_t"]))

    def test_external_cached_sharded_staging(self, monkeypatch, tmp_path):
        # the auto-residency external route (host pages) through the
        # per-chip staging == the global-put staging, tree for tree
        X, y = _make_xy(333, F=5, seed=6)
        path = tmp_path / "data.libsvm"
        with open(path, "w") as f:
            for i in range(len(y)):
                feats = " ".join(f"{j}:{X[i, j]:.6f}" for j in range(5))
                f.write(f"{y[i]:.0f} {feats}\n")

        def fit_one():
            m = HistGBT(mesh=local_mesh(8), **KW)
            m.fit_external(RowBlockIter.create(str(path)), num_col=5)
            return m

        with monkeypatch.context() as mp:
            mp.setattr(HistGBT, "_sharded_ingest_ok", lambda self: False)
            m_gl = fit_one()
        m_sh = fit_one()
        assert _trees_equal(m_gl.trees, m_sh.trees)


def _ingest_record(model, X, y, **kw):
    """One ``make_device_data``: the handle and the operation's record."""
    before = len(profiler.op_log())
    handle = model.make_device_data(X, y, **kw)
    if model._pending_warmup is not None:
        model._pending_warmup.join()
    (rec,) = [r for r in profiler.op_log()[before:]
              if r["name"] == "dmlc.ingest"]
    return handle, rec


class TestCutSortByFeatures:
    """ISSUE 52: on a mesh the rows go to the chips once, as row shards,
    one ``all_to_all`` makes them column shards, every chip sorts
    ``F / ndev`` columns of ALL rows: the same cuts to the bit."""

    NDEV = 4

    @pytest.mark.parametrize("n", [1001, 1000],
                             ids=["padded_rows", "whole_shards"])
    @pytest.mark.parametrize("F", [3, 5, 28])
    @pytest.mark.parametrize("mode", ["standard", "nan_on_one_chip",
                                      "row_weights"])
    def test_cuts_and_bins_are_a_one_device_models_bytes(self, mode, F, n):
        # F = 3: fewer columns than chips; 5: not a multiple of them;
        # n = 1001: three pad rows close the last shard and must never
        # reach the sort (they are zeros: they would move every quantile)
        X, y = _make_xy(n, F=F, seed=F)
        X[::7, 0] = 0.0                       # ties, and both zeros
        X[::5, 1] = -0.0
        weight = None
        if mode == "nan_on_one_chip":
            # chip 2's rows alone hold NaN: the mode is entered for all
            # of them, and the reserved bin is the NaN's
            S = -(-n // self.NDEV)
            hole = np.random.default_rng(1).random((S, F)) < 0.3
            X[2 * S:3 * S][hole] = np.nan
        elif mode == "row_weights":
            weight = np.random.default_rng(2).uniform(
                0.5, 2.0, size=n).astype(np.float32)
        m1 = HistGBT(mesh=local_mesh(1), **KW)
        h1, rec1 = _ingest_record(m1, X, y, weight=weight)
        m4 = HistGBT(mesh=local_mesh(self.NDEV), **KW)
        h4, rec4 = _ingest_record(m4, X, y, weight=weight)

        assert (rec1["counts"]["cuts_sort"], rec4["counts"]["cuts_sort"]) == (
            "whole", "features")
        assert rec4["counts"]["nan_scan"] == "device"
        assert m4._missing is m1._missing is (mode == "nan_on_one_chip")
        assert rec4["counts"]["missing_share"] == rec1["counts"][
            "missing_share"]
        c1, c4 = np.asarray(m1.cuts), np.asarray(m4.cuts)
        assert c1.dtype == c4.dtype and c1.shape == c4.shape
        assert c1.tobytes() == c4.tobytes()
        # the cuts a model keeps are not tied to the mesh, nor is what
        # is computed from them (a predict's bins): one device, uncommitted
        assert m4.cuts.devices() == {jax.devices()[0]}
        assert not m4.cuts.committed

        b1, b4 = np.asarray(h1["bins_t"]), np.asarray(h4["bins_t"])
        assert h4["n_padded"] == n + (-n) % self.NDEV == b4.shape[1]
        assert b1.dtype == b4.dtype
        assert b1.tobytes() == b4[:, :n].tobytes()
        if mode == "nan_on_one_chip":
            assert (b4[:, :n] == m4._miss_bin()).sum() == np.isnan(X).sum()
        # the pad rows are zero rows, binned like any other
        zero_bins = np.asarray(m1._bin_matrix(np.zeros((1, F), np.float32)))
        assert np.array_equal(b4[:, n:], np.repeat(zero_bins.T, b4.shape[1] - n,
                                                   axis=1))
        assert np.array_equal(np.asarray(h4["w_d"])[n:], np.zeros(b4.shape[1] - n))

        # a second handle from the kept cuts (the slab stream, a copy of
        # the cuts a chip) is the same matrix
        h4b, rec4b = _ingest_record(m4, X, y, weight=weight)
        assert rec4b["counts"]["cuts_sort"] == "none"
        assert np.array_equal(np.asarray(h4b["bins_t"]), b4)

    def test_no_chip_is_given_the_whole_matrix(self, monkeypatch):
        n, F, ndev = 1001, 28, self.NDEV
        X, y = _make_xy(n, F=F)
        S = -(-n // ndev)
        row_shard = S * F * 4
        col_shard = ndev * S * (-(-F // ndev)) * 4
        puts = []
        real_put = G._put_matrix

        def spy(M, sharding):
            puts.append((M.nbytes, sharding))
            return real_put(M, sharding)

        monkeypatch.setattr(G, "_put_matrix", spy)
        monkeypatch.setenv("DMLC_INGEST_CHUNK_ROWS", "100")
        m4 = HistGBT(mesh=local_mesh(ndev), **KW)
        seen = []
        real_cuts = G.compute_cuts

        def cuts_spy(x, *a, **kw):
            seen.extend(sh.data.shape for sh in x.addressable_shards)
            return real_cuts(x, *a, **kw)

        monkeypatch.setattr(G, "compute_cuts", cuts_spy)
        h4, rec = _ingest_record(m4, X, y)
        # the whole-matrix put is not taken: every chip is put its row
        # shard, in pieces of at most a slab's rows, every row once (the
        # last shard's three pad rows beside them)
        assert puts == []
        n_puts, _s, _l, put_bytes = rec["children"]["dmlc.ingest.put"]
        assert n_puts == ndev * -(-S // 100)
        assert put_bytes == ndev * row_shard == (n + 3) * F * 4
        assert rec["children"]["dmlc.ingest.put_wait"][0::3] == [
            n_puts, put_bytes]
        assert seen == [(S, F)] * ndev
        # nothing the cut program makes on a chip is larger than a row
        # shard plus a column shard (per-chip shapes: inside shard_map)
        fn = Q._mesh_summary_fn(m4.mesh, n, max(8 * KW["n_bins"], 64),
                                False, False)
        sizes = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                sizes.extend(v.aval.size * v.aval.dtype.itemsize
                             for v in eqn.outvars)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        jaxpr = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct(
            (ndev * S, F), np.float32)).jaxpr
        (mapped,) = jaxpr.eqns                 # the shard_map, alone
        for sub in jax.core.jaxprs_in_params(mapped.params):
            walk(sub)
        assert col_shard in sizes              # the sort's operand
        assert max(sizes) <= row_shard + col_shard, max(sizes)
        # what stays on a chip afterwards: its slice of the bin matrix
        assert max(sh.data.nbytes for sh in h4["bins_t"].addressable_shards
                   ) == S * F
        # one device, several slabs: the whole matrix, no sharding, as before
        m1 = HistGBT(mesh=local_mesh(1), **KW)
        m1.make_device_data(X, y)
        assert puts == [(X.nbytes, None)]

    @pytest.mark.parametrize("case", ["all_nan_column", "two_bins"])
    def test_the_missing_mode_errors_are_raised_as_before(self, case):
        # 1001 rows: the zero pad rows of the last shard are finite in
        # every column and must not hide a column without a value
        X, y = _make_xy(1001, F=5)
        X[::3, 0] = np.nan
        kw = dict(KW)
        if case == "all_nan_column":
            X[:, 3] = np.nan
            X[::2, 3] = np.inf
            message = "a feature is all-NaN: drop it or impute"
        else:
            kw["n_bins"] = 2
            message = "NaN features need n_bins >= 3"
        m4 = HistGBT(mesh=local_mesh(self.NDEV), **kw)
        with pytest.raises(Error, match=message):
            m4.make_device_data(X, y)
        assert m4.cuts is None and not m4._missing


class TestOneChipOracle:
    def test_nchip_fit_matches_1chip_oracle_bytes(self, monkeypatch,
                                                  tmp_path):
        # THE flagship contract: same global rows => identical ensemble
        # bytes, 1 chip vs 8 chips, via the deterministic histogram
        # reduction (DMLC_HIST_BLOCKS; plain psum's accumulation order
        # varies with mesh shape and CAN flip a near-tie split)
        monkeypatch.setenv("DMLC_HIST_BLOCKS", "8")
        X, y = _make_xy(1003, F=7, seed=1)
        cuts = compute_cuts(X, KW["n_bins"])
        devs = np.array(jax.devices())
        m1 = HistGBT(mesh=Mesh(devs[:1], ("data",)), **KW)
        m1.fit(X, y, cuts=cuts)
        m8 = HistGBT(mesh=Mesh(devs[:8], ("data",)), **KW)
        m8.fit(X, y, cuts=cuts)
        p1, p8 = tmp_path / "m1.gbt", tmp_path / "m8.gbt"
        m1.save_model(str(p1))
        m8.save_model(str(p8))
        assert p1.read_bytes() == p8.read_bytes()
        # and a third mesh shape for the invariance claim
        m2 = HistGBT(mesh=Mesh(devs[:2], ("data",)), **KW)
        m2.fit(X, y, cuts=cuts)
        assert _trees_equal(m1.trees, m2.trees)

    def test_nchip_oracle_survives_new_knobs(self, monkeypatch):
        # the ISSUE 12 levers must preserve the mesh-shape-invariant
        # fold: packed storage derives the SAME layout on every mesh
        # (occupancy counts are row-order independent) and lossguide
        # mirrors the per-block deterministic reduction
        monkeypatch.setenv("DMLC_HIST_BLOCKS", "8")
        monkeypatch.setenv("DMLC_BIN_PACK", "1")
        lg = dict(KW, grow_policy="lossguide")
        rng = np.random.default_rng(5)
        n = 1003
        X = rng.normal(size=(n, 7)).astype(np.float32)
        X[:, 2] = rng.integers(0, 3, n).astype(np.float32)
        X[:, 5] = rng.integers(0, 4, n).astype(np.float32)
        y = (X[:, 0] + X[:, 2] > 0.5).astype(np.float32)
        cuts = compute_cuts(X, KW["n_bins"])
        devs = np.array(jax.devices())
        m1 = HistGBT(mesh=Mesh(devs[:1], ("data",)), **lg)
        m1.fit(X, y, cuts=cuts)
        m8 = HistGBT(mesh=Mesh(devs[:8], ("data",)), **lg)
        m8.fit(X, y, cuts=cuts)
        assert m1._bin_layout is not None
        assert m1._bin_layout == m8._bin_layout   # identical layout
        assert _trees_equal(m1.trees, m8.trees)

    def test_deterministic_mode_prediction_parity(self, monkeypatch):
        # deterministic-mode trees predict identically from either mesh
        monkeypatch.setenv("DMLC_HIST_BLOCKS", "8")
        X, y = _make_xy(520, seed=9)
        cuts = compute_cuts(X, KW["n_bins"])
        devs = np.array(jax.devices())
        m1 = HistGBT(mesh=Mesh(devs[:1], ("data",)), **KW)
        m1.fit(X, y, cuts=cuts)
        m8 = HistGBT(mesh=Mesh(devs[:8], ("data",)), **KW)
        m8.fit(X, y, cuts=cuts)
        np.testing.assert_array_equal(
            m1.predict(X, output_margin=True),
            m8.predict(X, output_margin=True))


class TestOutOfCore:
    def test_iter_ingest_matches_incore_bytes(self, monkeypatch, tmp_path):
        # streamed tiny slabs (out-of-core shape) == in-core fit,
        # ensemble serialized byte-identically
        monkeypatch.setenv("DMLC_INGEST_CHUNK_ROWS", "128")
        X, y = _make_xy(1013, seed=5)
        n = len(y)
        m_it = HistGBT(mesh=local_mesh(8), **KW)

        def slabs():
            for lo in range(0, n, 160):    # misaligned with chunk AND S
                yield X[lo:lo + 160], y[lo:lo + 160], None

        dd = m_it.make_device_data_iter(slabs)
        m_it.fit_device(dd)
        m_ic = HistGBT(mesh=local_mesh(8), **KW)
        m_ic.fit(X, y, cuts=m_it.cuts)
        pa, pb = tmp_path / "it.gbt", tmp_path / "ic.gbt"
        m_it.save_model(str(pa))
        m_ic.save_model(str(pb))
        assert pa.read_bytes() == pb.read_bytes()

    def test_disk_row_iter_out_of_core(self, tmp_path):
        # the DiskRowIter/input_split page pipeline end to end: libsvm
        # -> #cache pages -> dense slabs -> sharded device ingest; the
        # handle must train and predict without X ever being needed
        X, y = _make_xy(801, F=5, seed=8)
        path = tmp_path / "big.libsvm"
        with open(path, "w") as f:
            for i in range(len(y)):
                feats = " ".join(f"{j}:{X[i, j]:.6f}" for j in range(5))
                f.write(f"{y[i]:.0f} {feats}\n")
        uri = f"{path}#{tmp_path}/cache.bin"

        def slabs():
            it = RowBlockIter.create(uri)
            return iter_dense_slabs(it, 5, 96)

        m = HistGBT(mesh=local_mesh(8), **KW)
        dd = m.make_device_data_iter(slabs, n_features=5)
        m.fit_device(dd)
        assert dd["n"] == 801 and dd["n_padded"] % 8 == 0
        assert len(m.trees) == KW["n_trees"]
        # same rows in-core with the sketch cuts => identical trees
        # (compare against the PARSED values: the libsvm text round
        # trip is not f32-exact, the oracle must see what disk saw)
        Xp = np.concatenate([np.array(xb) for xb, _, _ in slabs()])
        yp = np.concatenate([np.array(yb) for _, yb, _ in slabs()])
        m2 = HistGBT(mesh=local_mesh(8), **KW)
        m2.fit(Xp, yp, cuts=m.cuts)
        assert _trees_equal(m.trees, m2.trees)

    def test_iter_ingest_rejects_nan(self):
        X, y = _make_xy(64)
        X[3, 1] = np.nan
        m = HistGBT(mesh=local_mesh(8), **KW)
        with pytest.raises(Exception, match="NaN"):
            m.make_device_data_iter(lambda: iter([(X, y, None)]))


class TestPsumTraffic:
    def test_analytic_model_shape(self):
        # depth-1 tree: root only — [2, 1, F, B] f32
        assert hist_psum_bytes_per_round(1, 28, 256) == 2 * 28 * 256 * 4
        # sibling subtraction: each extra level adds 2 * 2^(l-1) * F * B * 4
        d6 = hist_psum_bytes_per_round(6, 28, 256)
        assert d6 == sum((2 * (1 if l == 0 else 1 << (l - 1))
                          * 28 * 256 * 4) for l in range(6))

    def test_counter_matches_model(self):
        from dmlc_core_tpu.base.metrics import default_registry

        X, y = _make_xy(512, seed=12)
        mesh = local_mesh(8)

        def psum_total():
            snap = default_registry().snapshot()["metrics"]
            m = snap.get("dmlc_histogram_psum_bytes_total")
            return (sum(s["value"] for s in m["series"]
                        if s["labels"].get("engine") == "incore")
                    if m else 0.0)

        before = psum_total()
        m8 = HistGBT(mesh=mesh, **KW)
        m8.fit(X, y)
        expect = KW["n_trees"] * hist_psum_bytes_per_round(
            KW["max_depth"], X.shape[1], KW["n_bins"])
        assert psum_total() - before == expect

    def test_counter_matches_model_packed(self, monkeypatch):
        # packed layout: the analytic model (and therefore the counter)
        # must price the STORAGE shape the psum actually syncs
        from dmlc_core_tpu.base.metrics import default_registry

        rng = np.random.default_rng(21)
        n, F = 512, 6
        X = rng.normal(size=(n, F)).astype(np.float32)
        X[:, 1] = rng.integers(0, 3, n).astype(np.float32)
        X[:, 3] = rng.integers(0, 2, n).astype(np.float32)
        X[:, 4] = rng.integers(0, 4, n).astype(np.float32)
        y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)

        def psum_total():
            snap = default_registry().snapshot()["metrics"]
            m = snap.get("dmlc_histogram_psum_bytes_total")
            return (sum(s["value"] for s in m["series"]
                        if s["labels"].get("engine") == "incore")
                    if m else 0.0)

        monkeypatch.setenv("DMLC_BIN_PACK", "1")
        before = psum_total()
        m8 = HistGBT(mesh=local_mesh(8), **KW)
        m8.fit(X, y)
        assert m8._bin_layout is not None      # the lever actually fired
        expect = KW["n_trees"] * hist_psum_bytes_per_round(
            KW["max_depth"], F, KW["n_bins"], layout=m8._bin_layout)
        assert psum_total() - before == expect

    def test_counter_matches_model_lossguide(self, monkeypatch):
        from dmlc_core_tpu.base.metrics import default_registry

        X, y = _make_xy(512, seed=14)

        def psum_total():
            snap = default_registry().snapshot()["metrics"]
            m = snap.get("dmlc_histogram_psum_bytes_total")
            return (sum(s["value"] for s in m["series"]
                        if s["labels"].get("engine") == "incore")
                    if m else 0.0)

        before = psum_total()
        m8 = HistGBT(mesh=local_mesh(8), grow_policy="lossguide",
                     max_leaves=4, **KW)
        m8.fit(X, y)
        expect = KW["n_trees"] * hist_psum_bytes_per_round(
            KW["max_depth"], X.shape[1], KW["n_bins"],
            grow_policy="lossguide", max_leaves=4)
        assert psum_total() - before == expect
        # the lever's win shows at depth: a budgeted deep tree syncs
        # far fewer built nodes than level-batched growth
        assert hist_psum_bytes_per_round(
            6, 28, 256, grow_policy="lossguide", max_leaves=8
        ) < hist_psum_bytes_per_round(6, 28, 256)

    def test_counter_silent_on_one_chip(self):
        from dmlc_core_tpu.base.metrics import default_registry

        X, y = _make_xy(256, seed=13)

        def psum_total():
            snap = default_registry().snapshot()["metrics"]
            m = snap.get("dmlc_histogram_psum_bytes_total")
            return (sum(s["value"] for s in m["series"]) if m else 0.0)

        before = psum_total()
        m1 = HistGBT(mesh=local_mesh(1), **KW)
        m1.fit(X, y)
        assert psum_total() == before      # no cross-chip traffic

    def test_device_count_helper(self):
        assert device_count(local_mesh(8)) == 8
        assert device_count(local_mesh(1)) == 1
