"""Tests for device ops (histogram, quantile) and the hist-GBT flagship.

Oracles: numpy reference histogram; monotone loss decrease; near-perfect
fit on separable synthetic data; sharded-vs-single-device equivalence
(the histogram psum correctness check — BASELINE config 1's semantics)."""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu.models import HistGBT
from dmlc_core_tpu.ops.histogram import build_histogram, reference_histogram
from dmlc_core_tpu.ops.quantile import (apply_bins, apply_bins_missing, apply_bins_t,
                                        compute_cuts, local_summary, merge_summaries)
from dmlc_core_tpu.parallel.mesh import local_mesh


class TestHistogram:
    def test_segment_matches_numpy_oracle(self, rng):
        n, F, B, N = 500, 7, 16, 4
        bins = rng.integers(0, B, size=(n, F)).astype(np.int32)
        node = rng.integers(0, N, size=n).astype(np.int32)
        g = rng.normal(size=n).astype(np.float32)
        h = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
        out = np.asarray(build_histogram(
            jnp.asarray(bins), jnp.asarray(node), jnp.asarray(g), jnp.asarray(h),
            N, B, "segment"))
        ref = reference_histogram(bins, node, g, h, N, B)
        np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-2)

    def test_pallas_matches_numpy_oracle(self, rng):
        # n_bins must be lane-aligned (%128) for the kernel; off-TPU the
        # pallas_call runs in interpret mode so the kernel logic (iota
        # compares, masking, grid accumulation) is exercised in CI
        from dmlc_core_tpu.ops.histogram import _pallas_ok

        n, F, B, N = 1100, 3, 128, 4   # n not a tile multiple → pad path
        assert _pallas_ok(B, F, N)
        bins = rng.integers(0, B, size=(n, F)).astype(np.int32)
        node = rng.integers(0, N, size=n).astype(np.int32)
        node[::5] = -1                 # padding/pruned rows must drop out
        g = rng.normal(size=n).astype(np.float32)
        h = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
        out = np.asarray(build_histogram(
            jnp.asarray(bins), jnp.asarray(node), jnp.asarray(g), jnp.asarray(h),
            N, B, "pallas"))
        ref = reference_histogram(bins, node, g, h, N, B)
        np.testing.assert_allclose(out, ref, atol=2e-2, rtol=1e-2)  # bf16 dot

    def test_lo_factor_table_and_model(self):
        # n_bins=256 answers come from the v5e sweep table; other bin
        # counts from the 5A+2lo op model. The MXU work A*lo is invariant
        # in lo, so any answer must keep lo*ceil(B/lo) >= B (coverage).
        from dmlc_core_tpu.ops.histogram import _LO_MEASURED_256, _lo_factor

        for n_build, want in _LO_MEASURED_256.items():
            assert _lo_factor(n_build, 256) == want
        for n_nodes in (1, 2, 4, 32, 64):
            for n_bins in (64, 128, 512):
                lo = _lo_factor(n_nodes, n_bins)
                assert lo <= max(n_bins, 8)
                assert lo * (-(-n_bins // lo)) >= n_bins

    def test_pallas_ok_vmem_guard(self):
        # calibrated VMEM-stack guard: the default tile takes all 28
        # features in one block at every default level; at tile 65536
        # (measured 16MB scoped-vmem OOM on v5e at 10M rows with all 28
        # in one block) the budgets admit a block of 8, never the whole
        # matrix, so nothing compiles what Mosaic refused
        from dmlc_core_tpu.ops.histogram import _TILE_ROWS, _pallas_ok

        for n_build in (1, 2, 4, 8, 16):
            assert _pallas_ok(256, 28, n_build, 1, _TILE_ROWS) == 28
        assert _pallas_ok(256, 28, 1, 1, 65536) == 8
        # int32 bins (>256 bin counts) scale the tile budget too
        assert _pallas_ok(512, 28, 1, 4, _TILE_ROWS)

    @pytest.mark.parametrize("n_nodes", [1, 2, 4, 8, 16])
    def test_pallas_partial_tiles_and_masked_rows(self, rng, n_nodes):
        # the kernel wrapper itself, at every build width of a depth-6
        # tree: 700 rows over 256-row tiles = the pad path and three
        # partial-width tiles accumulating into one block, with masked
        # rows that must drop out — against the numpy oracle (interpret
        # mode off-TPU).  tile_rows=256 is a static arg of its own, so
        # the jit cache cannot serve another test's trace.
        import dmlc_core_tpu.ops.histogram as H

        n, F, B = 700, 3, 128
        bins = rng.integers(0, B, size=(n, F)).astype(np.int32)
        node = rng.integers(0, n_nodes, size=n).astype(np.int32)
        node[::7] = -1                 # masked rows must drop out
        g = rng.normal(size=n).astype(np.float32)
        h = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
        out = np.asarray(H._hist_pallas(
            jnp.asarray(bins), jnp.asarray(node), jnp.asarray(g),
            jnp.asarray(h), n_nodes, B, 256))
        ref = reference_histogram(bins, node, g, h, n_nodes, B)
        np.testing.assert_allclose(out, ref, atol=2e-2, rtol=1e-2)

    def test_pallas_guard(self):
        from dmlc_core_tpu.ops.histogram import _pallas_ok

        # the factored kernel handles any n_bins (incl. unaligned); only a
        # VMEM blow-up (huge F·N·B accumulator) must be rejected
        assert _pallas_ok(32, 8)
        assert _pallas_ok(128, 8)
        assert _pallas_ok(200, 5)      # unaligned bins OK now
        assert _pallas_ok(256, 28)     # HIGGS shape
        assert _pallas_ok(256, 28, n_nodes=32)
        assert not _pallas_ok(256, 512, n_nodes=64)  # accumulator >> VMEM

    def test_negative_node_rows_ignored(self, rng):
        n, F, B, N = 100, 3, 8, 2
        bins = rng.integers(0, B, size=(n, F)).astype(np.int32)
        node = rng.integers(0, N, size=n).astype(np.int32)
        node[::3] = -1
        g = np.ones(n, np.float32)
        h = np.ones(n, np.float32)
        out = np.asarray(build_histogram(
            jnp.asarray(bins), jnp.asarray(node), jnp.asarray(g), jnp.asarray(h), N, B))
        assert out[0].sum() == pytest.approx((node >= 0).sum() * F)


def _edge_matrix(rng, n, cuts):
    """[n, F] float32 holding, per feature, what a digitizer gets wrong
    first: every cut, its float32 neighbours either side, ±0.0 (the
    middle cut is 0.0), ±inf, denormals, NaN, and plain draws."""
    cols = []
    for c in cuts:
        pool = np.concatenate([
            c, np.nextafter(c, np.float32(-np.inf)),
            np.nextafter(c, np.float32(np.inf)),
            np.float32([-0.0, 0.0, -np.inf, np.inf, np.nan,
                        1e-45, -1e-45, 1e-40, -1e-40]),
            rng.normal(size=64).astype(np.float32)])
        # the specials first, so that even a short column draws on them
        pool = np.concatenate([pool[3 * len(c):], pool[:3 * len(c)]])
        take = rng.permutation(len(pool))[:n] if n < len(pool) else (
            np.concatenate([np.arange(len(pool)),
                            rng.integers(0, len(pool), n - len(pool))]))
        cols.append(pool[take])
    return np.stack(cols, axis=1).astype(np.float32)


def _edge_cuts(rng, F, n_cuts):
    """[F, n_cuts] strictly increasing float32 cuts with 0.0 among them."""
    c = np.sort(rng.normal(size=(F, n_cuts)).astype(np.float32), axis=1)
    if n_cuts:
        c[:, n_cuts // 2] = 0.0
        c[:, :n_cuts // 2] = -np.abs(c[:, :n_cuts // 2]) - np.float32(1e-3)
        c[:, n_cuts // 2 + 1:] = np.abs(c[:, n_cuts // 2 + 1:]) + np.float32(1e-3)
        c = np.sort(c, axis=1)
    return c


def _tied_matrix(rng, n, F):
    """[n, F] float32 on which a sort has ties to break: long runs of
    duplicates, both zeros, denormals (XLA compares them as zero, so
    they tie with the zeros), ±inf; the last column partly NaN and the
    one before it all NaN."""
    specials = np.float32([0.0, -0.0, 1e-45, -1e-45, 1e-40, -1e-40,
                           np.inf, -np.inf, 0.25, 0.25, 0.25, -1.5])
    x = rng.normal(size=(n, F)).astype(np.float32)
    for f in range(F):
        kind = f % 4
        if kind == 0:                       # mostly ties, some draws
            runs = rng.random(n) < 0.8
            x[runs, f] = specials[rng.integers(0, len(specials), runs.sum())]
        elif kind == 1:                     # a few values, long runs
            x[:, f] = np.round(x[:, f] * 2) / 2
        elif kind == 2:                     # one atom and the zeros
            x[:, f] = np.where(rng.random(n) < 0.6, np.float32(-0.0),
                               np.where(rng.random(n) < 0.5, 0.0, x[:, f]))
    if F > 1:
        x[::3, F - 1] = np.nan
    if F > 2:
        x[:, F - 2] = np.nan
    return x


@partial(jax.jit, static_argnums=(1,))
def _stable_summary(x, n_summary):
    """``local_summary``'s unweighted path as it stood before ISSUE 38,
    word for word: ``jnp.quantile`` sorts with ``is_stable=True``."""
    qs = jnp.linspace(0.0, 1.0, n_summary)
    return jnp.quantile(x, qs, axis=0).T


class TestQuantile:
    @pytest.mark.parametrize("n", [1, 127, 4096])
    @pytest.mark.parametrize("F", [1, 28, 33])
    @pytest.mark.parametrize("n_bins", [2, 16, 256, 257, 1024])
    @pytest.mark.parametrize("missing", [False, True])
    def test_bins_equal_numpy_searchsorted(self, missing, n_bins, F, n):
        # element for element, every float32 class: the count of cuts
        # not above the value IS searchsorted(side="right"); NaN lands in
        # n_cuts, or in the reserved bin in missing mode (whose cuts are
        # one narrower: HistGBT's cut-width invariant)
        rng = np.random.default_rng([n_bins, F, n, missing])
        n_cuts = n_bins - 2 if missing else n_bins - 1
        cuts = _edge_cuts(rng, F, n_cuts)
        x = _edge_matrix(rng, n, cuts)

        def numpy_bins(v):
            b = np.stack([np.searchsorted(cuts[f], v[:, f], side="right")
                          for f in range(F)], axis=1)
            assert (b[np.isnan(v)] == n_cuts).all()      # numpy's NaN rule
            if missing:
                b[np.isnan(v)] = n_bins - 1
            return b

        want = numpy_bins(x)
        # XLA compares denormals as zero (CPU and TPU, the binary search
        # before this count alike): a denormal may bin as 0.0 does
        denormal = (x != 0) & (np.abs(x) < np.finfo(np.float32).tiny)
        as_zero = numpy_bins(np.where(denormal, np.float32(0), x))
        xd, cd = jnp.asarray(x), jnp.asarray(cuts)
        if missing:
            got = apply_bins_missing(xd, cd, n_bins - 1)
            got_t = apply_bins_t(xd, cd, miss_bin=n_bins - 1)
        else:
            got = apply_bins(xd, cd)
            got_t = apply_bins_t(xd, cd)
        assert got.dtype == got_t.dtype == (
            jnp.uint8 if n_bins <= 256 else jnp.int32)
        got = np.asarray(got)
        np.testing.assert_array_equal(np.asarray(got_t), got.T)
        np.testing.assert_array_equal(np.where(denormal, want, got), want)
        assert ((got == want) | (got == as_zero))[denormal].all()

    @pytest.mark.parametrize("n_summary", [64, 2048])
    @pytest.mark.parametrize("F", [1, 28, 33])
    @pytest.mark.parametrize("n", [1, 2, 127, 4096, 100003])
    def test_key_only_summary_equals_jnp_quantile(self, n, F, n_summary):
        # ISSUE 38: the sort of the keys alone (is_stable=False) changes
        # no value of the summary, on ties of every kind
        x = jnp.asarray(_tied_matrix(np.random.default_rng([n, F]), n, F))
        got = np.asarray(local_summary(x, None, n_summary))
        want = np.asarray(_stable_summary(x, n_summary))
        assert got.shape == want.shape == (F, n_summary)
        assert got.dtype == want.dtype == np.float32
        assert np.array_equal(got, want, equal_nan=True)
        if F > 1:
            assert np.isnan(got[F - 1]).all()        # the NaN guard's column
            assert F == 2 or np.isnan(got[F - 2]).all()

    @pytest.mark.parametrize("F", [1, 28, 33])
    @pytest.mark.parametrize("n", [1, 2, 127, 4096, 100003])
    def test_cuts_and_bins_equal_the_stable_sorts(self, n, F):
        # ... hence the cuts and the bins: bit for bit (merge_summaries'
        # guard adds a +0.0 to every cut, so not even a zero's sign moves)
        x = _tied_matrix(np.random.default_rng([F, n]), n, F)
        cuts = np.asarray(compute_cuts(x, n_bins=256))
        want = np.asarray(merge_summaries(
            _stable_summary(jnp.asarray(x), 2048)[None], 256))
        assert cuts.shape == (F, 255)
        assert np.array_equal(cuts.view(np.uint32), want.view(np.uint32))
        np.testing.assert_array_equal(
            np.asarray(apply_bins_t(jnp.asarray(x), jnp.asarray(cuts))),
            np.asarray(apply_bins_t(jnp.asarray(x), jnp.asarray(want))))

    def test_cuts_monotone_and_binning_balanced(self, rng):
        x = rng.normal(size=(10000, 3)).astype(np.float32)
        cuts = compute_cuts(x, n_bins=16)
        c = np.asarray(cuts)
        assert c.shape == (3, 15)
        assert (np.diff(c, axis=1) > 0).all()
        bins = np.asarray(apply_bins(jnp.asarray(x), cuts))
        assert bins.min() >= 0 and bins.max() <= 15
        # roughly uniform occupancy on smooth data
        counts = np.bincount(bins[:, 0], minlength=16)
        assert counts.min() > 10000 / 16 * 0.5

    def test_weighted_summary_shifts(self):
        x = np.linspace(0, 1, 1000).astype(np.float32)[:, None]
        w = np.where(x[:, 0] > 0.9, 100.0, 1.0).astype(np.float32)
        s_unw = np.asarray(local_summary(jnp.asarray(x), None, 16))
        s_w = np.asarray(local_summary(jnp.asarray(x), jnp.asarray(w), 16))
        assert np.median(s_w) > np.median(s_unw)  # mass pulled to the tail

    def test_merge_matches_global(self, rng):
        # splitting rows over "workers" then merging ≈ global quantiles
        x = rng.normal(size=(8000, 2)).astype(np.float32)
        parts = np.split(x, 4)
        summaries = jnp.stack([local_summary(jnp.asarray(p), None, 256) for p in parts])
        cuts_merged = np.asarray(merge_summaries(summaries, 16))
        cuts_global = np.asarray(compute_cuts(x, n_bins=16))
        np.testing.assert_allclose(cuts_merged, cuts_global, atol=0.05)

    def test_constant_feature_ok(self):
        x = np.ones((100, 2), np.float32)
        cuts = compute_cuts(x, n_bins=8)
        bins = np.asarray(apply_bins(jnp.asarray(x), cuts))
        assert (bins >= 0).all() and (bins < 8).all()

    def test_atom_dominated_cuts_strictly_increase(self):
        # A sparse column densified to 0.0 puts a RUN of quantile targets
        # on one atom; the guard must fan the whole run apart (the old
        # single-pass bump left runs >= 3 non-strict).
        x = np.zeros((1000, 2), np.float32)
        x[:30, 0] = np.linspace(1, 2, 30)
        x[:, 1] = np.linspace(-1, 1, 1000)
        cuts = np.asarray(compute_cuts(x, n_bins=32))
        assert (np.diff(cuts, axis=1) > 0).all()
        # the fanned copies stay below the next real value: rows at the
        # atom and rows at 1.0 must still separate
        bins = np.asarray(apply_bins(jnp.asarray(x), jnp.asarray(cuts)))
        assert bins[:30, 0].min() > bins[31:, 0].max()

    def test_missing_all_nan_on_one_shard(self, rng):
        # A feature entirely NaN on ONE worker but finite globally must
        # not poison the merged cuts (round-4 advisor finding: the NaN
        # sentinel row used to propagate through jnp.quantile and
        # collapse the feature to bin 0 on every worker).
        x0 = rng.normal(size=(500, 3)).astype(np.float32)
        x0[:, 1] = np.nan                      # worker 0: f1 all missing
        x1 = rng.normal(size=(500, 3)).astype(np.float32)
        s0 = local_summary(jnp.asarray(x0), None, 128, True)
        s1 = local_summary(jnp.asarray(x1), None, 128, True)
        assert np.isnan(np.asarray(s0)[1]).all()      # sentinel row
        assert np.isfinite(np.asarray(s0)[[0, 2]]).all()
        cuts = np.asarray(merge_summaries(jnp.stack([s0, s1]), 16))
        assert np.isfinite(cuts).all()
        assert (np.diff(cuts, axis=1) > 0).all()
        # f1's cuts must equal what worker 1 alone would produce: the
        # NaN row contributes zero points to the merge
        solo = np.asarray(merge_summaries(s1[None], 16))
        np.testing.assert_allclose(cuts[1], solo[1], rtol=1e-6)
        # and the same end to end through compute_cuts + a fake gather
        def gather(s):
            return np.stack([np.asarray(local_summary(
                jnp.asarray(x0), None, s.shape[1], True)), s])
        cuts2 = np.asarray(compute_cuts(
            x1, n_bins=16, n_summary=128, allgather_fn=gather, missing=True))
        assert np.isfinite(cuts2).all()

    def test_missing_all_nan_everywhere_degrades_finite(self, rng):
        # Globally all-NaN features are rejected by callers up front;
        # the merge itself must still emit finite increasing cuts (not
        # NaN, which would silently bin every value to 0 downstream).
        x = np.full((50, 2), np.nan, np.float32)
        x[:, 0] = rng.normal(size=50)
        s = local_summary(jnp.asarray(x), None, 64, True)
        cuts = np.asarray(merge_summaries(s[None], 8))
        assert np.isfinite(cuts).all()
        assert (np.diff(cuts, axis=1) > 0).all()


def _synthetic(n=2000, f=10, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    margin = 2.0 * X[:, 0] - 1.5 * X[:, 1] + X[:, 2] * X[:, 3]
    y = (margin + 0.1 * rng.normal(size=n) > 0).astype(np.float32)
    return X, y


class TestHistGBT:
    def test_loss_decreases_and_fits(self):
        X, y = _synthetic()
        model = HistGBT(n_trees=20, max_depth=4, learning_rate=0.5, n_bins=64)
        model.fit(X, y)
        p10 = model.predict(X, n_trees=10)
        p20 = model.predict(X)
        def logloss(p):
            eps = 1e-7
            return -np.mean(y * np.log(p + eps) + (1 - y) * np.log(1 - p + eps))
        assert logloss(p20) < logloss(p10) < np.log(2)  # better than chance, improving
        acc = ((p20 > 0.5) == y).mean()
        assert acc > 0.93, acc

    def test_regression_objective(self):
        rng = np.random.default_rng(1)
        X = rng.uniform(-1, 1, size=(1500, 5)).astype(np.float32)
        ytrue = 3.0 * X[:, 0] + np.sin(3 * X[:, 1])
        model = HistGBT(n_trees=30, max_depth=4, learning_rate=0.3,
                        objective="reg:squarederror", n_bins=64)
        model.fit(X, ytrue.astype(np.float32))
        pred = model.predict(X)
        rmse = np.sqrt(np.mean((pred - ytrue) ** 2))
        assert rmse < 0.35, rmse

    def test_sharded_equals_replicated(self):
        """THE DP-correctness oracle: training on the 8-device mesh (psum
        histogram sync) must produce the same trees as a 1-device mesh."""
        X, y = _synthetic(n=1024, f=6, seed=3)
        m8 = HistGBT(n_trees=5, max_depth=3, n_bins=32, mesh=local_mesh())
        m1 = HistGBT(n_trees=5, max_depth=3, n_bins=32, mesh=local_mesh(1))
        m8.fit(X, y)
        m1.fit(X, y)
        for t8, t1 in zip(m8.trees, m1.trees):
            np.testing.assert_array_equal(t8["feat"], t1["feat"])
            np.testing.assert_array_equal(t8["thr"], t1["thr"])
            np.testing.assert_allclose(t8["leaf"], t1["leaf"], rtol=1e-4, atol=1e-5)

    def test_uneven_rows_padded(self):
        X, y = _synthetic(n=1001, f=4, seed=4)  # not divisible by 8
        model = HistGBT(n_trees=3, max_depth=3, n_bins=32)
        model.fit(X, y)
        assert model.predict(X).shape == (1001,)

    def test_weights_respected(self):
        # duplicate a subpopulation via weights: with identical binning, a
        # weighted fit must equal a fit on physically replicated rows
        X, y = _synthetic(n=400, f=4, seed=5)
        w = np.ones(400, np.float32)
        w[:50] = 3.0
        cuts = compute_cuts(X, n_bins=32)
        mw = HistGBT(n_trees=5, max_depth=3, n_bins=32, mesh=local_mesh(1))
        mw.fit(X, y, weight=w, cuts=cuts)
        Xr = np.concatenate([X[:50]] * 3 + [X[50:]])
        yr = np.concatenate([y[:50]] * 3 + [y[50:]])
        mr = HistGBT(n_trees=5, max_depth=3, n_bins=32, mesh=local_mesh(1))
        mr.fit(Xr, yr, cuts=cuts)
        for tw, tr in zip(mw.trees, mr.trees):
            np.testing.assert_array_equal(tw["feat"], tr["feat"])
            np.testing.assert_array_equal(tw["thr"], tr["thr"])
            np.testing.assert_allclose(tw["leaf"], tr["leaf"], rtol=1e-4, atol=1e-5)

    def test_margin_output_and_base_score(self):
        X, y = _synthetic(n=256, f=4, seed=7)
        model = HistGBT(n_trees=2, max_depth=2, n_bins=16, base_score=0.5)
        model.fit(X, y)
        margin = model.predict(X, output_margin=True)
        prob = model.predict(X)
        np.testing.assert_allclose(prob, 1 / (1 + np.exp(-margin)), rtol=1e-5)

    def test_param_validation(self):
        from dmlc_core_tpu.base.logging import Error

        with pytest.raises(Error):
            HistGBT(max_depth=50)
        with pytest.raises(Error):
            HistGBT(objective="multi:softmax", num_class=0)


class TestGBTExtras:
    def _data(self, n=6000, F=6, seed=0):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, F)).astype(np.float32)
        y = (X[:, 0] * X[:, 1] + 0.5 * X[:, 2] > 0).astype(np.float32)
        return X, y

    def test_save_load_round_trip(self, tmp_path):
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data()
        m = HistGBT(n_trees=8, max_depth=3, n_bins=32)
        m.fit(X, y)
        uri = str(tmp_path / "model.bin")
        m.save_model(uri)
        m2 = HistGBT.load_model(uri)
        np.testing.assert_array_equal(m2.predict(X, output_margin=True),
                                      m.predict(X, output_margin=True))
        assert m2.param.n_trees == 8 and m2.param.max_depth == 3

    def test_load_rejects_garbage(self, tmp_path):
        import pytest
        from dmlc_core_tpu.base.logging import Error
        from dmlc_core_tpu.models import HistGBT

        bad = tmp_path / "bad.bin"
        bad.write_bytes(b"NOTAMODELxxxx")
        with pytest.raises(Error):
            HistGBT.load_model(str(bad))

    @pytest.mark.slow
    def test_subsample_colsample_train(self):
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data()
        m = HistGBT(n_trees=25, max_depth=4, n_bins=32,
                    subsample=0.7, colsample_bytree=0.7, seed=3,
                    learning_rate=0.3)
        m.fit(X, y)
        acc = ((m.predict(X) > 0.5) == y).mean()
        assert acc > 0.85, acc
        # same seed → identical model
        m2 = HistGBT(n_trees=25, max_depth=4, n_bins=32,
                     subsample=0.7, colsample_bytree=0.7, seed=3,
                     learning_rate=0.3)
        m2.fit(X, y)
        np.testing.assert_array_equal(m.predict(X, output_margin=True),
                                      m2.predict(X, output_margin=True))

    def test_colsample_restricts_features(self):
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data(F=8)
        m = HistGBT(n_trees=10, max_depth=3, n_bins=32,
                    colsample_bytree=0.25, seed=1)
        m.fit(X, y)
        # ⌈0.25·8⌉ = 2 features available per tree → per-tree split
        # features must come from ≤2 distinct features
        B = m.param.n_bins
        for tree in m.trees:
            used = set()
            for level in range(tree["feat"].shape[0]):
                n_nodes = 1 << level
                feat = tree["feat"][level][:n_nodes]
                thr = tree["thr"][level][:n_nodes]
                used.update(feat[thr < B - 1].tolist())
            assert len(used) <= 2, used

    def test_early_stopping(self):
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data(n=4000)
        Xv, yv = self._data(n=2000, seed=9)
        m = HistGBT(n_trees=200, max_depth=3, n_bins=32, learning_rate=0.5)
        m.fit(X, y, eval_set=(Xv, yv), early_stopping_rounds=10)
        assert m.best_iteration is not None and m.best_score is not None
        assert len(m.trees) < 200            # actually stopped early
        # default predict uses best_iteration+1 trees
        pd_best = m.predict(Xv, output_margin=True)
        pd_explicit = m.predict(Xv, output_margin=True,
                                n_trees=m.best_iteration + 1)
        np.testing.assert_array_equal(pd_best, pd_explicit)

    def test_host_binned_fit_matches_device_binned(self, rng, monkeypatch):
        """DMLC_TPU_BIN_BACKEND=cpu bins in-core fits on the host backend
        (uint8 upload instead of f32 — 4x less transfer); same
        cuts → same bins → identical trees.  conftest pins CPU, so both
        branches compute on one backend and exactness is deterministic."""
        X = rng.normal(size=(800, 6)).astype(np.float32)
        y = (X[:, 0] - X[:, 1] > 0).astype(np.float32)
        models = {}
        for pinned in (False, True):
            if pinned:
                monkeypatch.setenv("DMLC_TPU_BIN_BACKEND", "cpu")
            else:
                monkeypatch.delenv("DMLC_TPU_BIN_BACKEND", raising=False)
            m = HistGBT(n_trees=5, max_depth=3, n_bins=32)
            m.fit(X, y)
            models[pinned] = m
        for t0, t1 in zip(models[False].trees, models[True].trees):
            np.testing.assert_array_equal(t0["feat"], t1["feat"])
            np.testing.assert_array_equal(t0["thr"], t1["thr"])
            np.testing.assert_allclose(t0["leaf"], t1["leaf"], rtol=1e-5)

    def test_predict_leaf_reconstructs_margins(self, rng):
        """pred_leaf oracle: summing each tree's leaf value at the
        reported leaf index must reproduce predict(output_margin=True)
        exactly — the leaf indices ARE the descent predict performs."""
        X = rng.normal(size=(300, 5)).astype(np.float32)
        y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
        m = HistGBT(n_trees=6, max_depth=3, n_bins=16)
        m.fit(X, y)
        leaves = m.predict_leaf(X)
        assert leaves.shape == (300, 6)
        assert leaves.min() >= 0 and leaves.max() < 2 ** 3
        margin = np.full(300, m.param.base_score, np.float32)
        for t, tree in enumerate(m.trees):
            margin += tree["leaf"][leaves[:, t]]
        np.testing.assert_allclose(
            margin, m.predict(X, output_margin=True), rtol=1e-5,
            atol=1e-6)

    def test_predict_leaf_multiclass(self, rng):
        X = rng.normal(size=(200, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32) + (X[:, 1] > 0)
        m = HistGBT(n_trees=3, max_depth=2, n_bins=16,
                    objective="multi:softmax", num_class=3)
        m.fit(X, y)
        leaves = m.predict_leaf(X)
        assert leaves.shape == (200, 3, 3)          # [n, T, K]
        margin = np.full((200, 3), m.param.base_score, np.float32)
        for t, tree in enumerate(m.trees):
            for c in range(3):
                margin[:, c] += tree["leaf"][c][leaves[:, t, c]]
        np.testing.assert_allclose(
            margin, m.predict(X, output_margin=True), rtol=1e-5,
            atol=1e-6)

    def test_dump_model_text(self):
        """The text dump is structurally faithful: 2^depth leaves per
        tree whose values equal the stored leaf array, split thresholds
        are real cut values for the named feature, and a hand-descent
        of the dumped rules reproduces predict() on a probe row."""
        import re
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data()
        m = HistGBT(n_trees=3, max_depth=3, n_bins=32, learning_rate=0.3)
        m.fit(X, y)
        dump = m.dump_model(with_stats=True)
        assert dump.count("booster[") == 3
        cuts = np.asarray(m.cuts)
        for ti, tree in enumerate(m.trees):
            sec = dump.split(f"booster[{ti}]:")[1].split("booster[")[0]
            leaves = re.findall(r"(\d+):leaf=([-\d.e+]+)", sec)
            assert len(leaves) == 8
            np.testing.assert_allclose(
                [float(v) for _, v in leaves], tree["leaf"],
                rtol=1e-4, atol=1e-6)
            for f, thr in re.findall(r"\[f(\d+)<([-\d.e+]+)\]", sec):
                f, thr = int(f), float(thr)
                assert np.isclose(cuts[f], thr, rtol=1e-3,
                                  atol=1e-5).any(), (f, thr)
        # hand-descend the dumped rules for one row, tree 0
        sec = dump.split("booster[0]:")[1].split("booster[")[0]
        nodes = {}
        for line in sec.strip().splitlines():
            line = line.strip()
            mm = re.match(r"(\d+):\[f(\d+)<([-\d.e+]+)\] yes=(\d+),no=(\d+)",
                          line)
            if mm:
                nodes[int(mm.group(1))] = (
                    int(mm.group(2)), float(mm.group(3)),
                    int(mm.group(4)), int(mm.group(5)))
                continue
            mm = re.match(r"(\d+):passthrough yes=(\d+),no=(\d+)", line)
            if mm:
                nodes[int(mm.group(1))] = (None, None,
                                           int(mm.group(2)),
                                           int(mm.group(3)))
                continue
            mm = re.match(r"(\d+):leaf=([-\d.e+]+)", line)
            nodes[int(mm.group(1))] = ("leaf", float(mm.group(2)))
        row = X[7]
        nid = 0
        while nodes[nid][0] != "leaf":
            f, thr, yes, no = nodes[nid]
            nid = yes if (f is None or row[f] < thr) else no
        margin1 = nodes[nid][1]
        # predict with ONLY tree 0: margin = base + leaf contribution
        got = m.predict(row[None], output_margin=True, n_trees=1)[0]
        np.testing.assert_allclose(got, m.param.base_score + margin1,
                                   rtol=1e-4, atol=1e-6)
        # multiclass dump: per-class sections with full leaf layers
        rng = np.random.default_rng(3)
        Xm = rng.normal(size=(600, 4)).astype(np.float32)
        ym = (Xm[:, 0] > 0).astype(np.float32) + (Xm[:, 1] > 0.7)
        mm3 = HistGBT(n_trees=2, max_depth=2, n_bins=16, num_class=3,
                      objective="multi:softmax")
        mm3.fit(Xm, ym)
        d3 = mm3.dump_model()
        assert d3.count("class[") == 6          # 2 trees x 3 classes
        assert d3.count(":leaf=") == 6 * 4      # 2^2 leaves per section
        # feature_names replaces the f<N> placeholders (fmap role) and
        # validates its length
        names = [f"col_{i}" for i in range(X.shape[1])]
        dn = m.dump_model(feature_names=names)
        assert "[col_" in dn
        assert "[f0<" not in dn
        from dmlc_core_tpu.base.logging import Error
        with pytest.raises(Error):
            m.dump_model(feature_names=["just_one"])

    def test_feature_importances(self):
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data(F=6)
        m = HistGBT(n_trees=15, max_depth=3, n_bins=32)
        m.fit(X, y)
        imp = m.feature_importances()
        assert imp.shape == (6,)
        # informative features (0,1,2) must dominate the noise ones
        assert imp[:3].sum() > imp[3:].sum()

    @pytest.mark.slow
    def test_continue_training(self, tmp_path):
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data()
        full = HistGBT(n_trees=20, max_depth=3, n_bins=32, learning_rate=0.3)
        full.fit(X, y)

        half = HistGBT(n_trees=10, max_depth=3, n_bins=32, learning_rate=0.3)
        half.fit(X, y)
        uri = str(tmp_path / "half.bin")
        half.save_model(uri)
        cont = HistGBT.load_model(uri)
        cont.param.init({"n_trees": 10})
        cont.fit(X, y)                       # 10 more rounds on top
        assert len(cont.trees) == 20
        np.testing.assert_allclose(
            cont.predict(X, output_margin=True),
            full.predict(X, output_margin=True), rtol=1e-4, atol=1e-5)

    def test_early_stop_state_survives_save_load(self, tmp_path):
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data(n=4000)
        Xv, yv = self._data(n=2000, seed=9)
        m = HistGBT(n_trees=200, max_depth=3, n_bins=32, learning_rate=0.5)
        m.fit(X, y, eval_set=(Xv, yv), early_stopping_rounds=10)
        uri = str(tmp_path / "es.bin")
        m.save_model(uri)
        m2 = HistGBT.load_model(uri)
        assert m2.best_iteration == m.best_iteration
        np.testing.assert_array_equal(m2.predict(Xv, output_margin=True),
                                      m.predict(Xv, output_margin=True))

    def test_subsample_zero_rejected(self):
        import pytest
        from dmlc_core_tpu.base.logging import Error
        from dmlc_core_tpu.models import HistGBT

        with pytest.raises(Error):
            HistGBT(subsample=0.0)

    def test_external_memory_sampling(self, tmp_path):
        from dmlc_core_tpu.data.iter import RowBlockIter
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data(n=2000, F=6)
        svm = tmp_path / "t.svm"
        with open(svm, "w") as f:
            for i in range(len(y)):
                feats = " ".join(f"{j}:{X[i, j]:.5f}" for j in range(6))
                f.write(f"{y[i]:.0f} {feats}\n")
        it = RowBlockIter.create(str(svm), 0, 1, "libsvm")
        m = HistGBT(n_trees=10, max_depth=3, n_bins=32,
                    colsample_bytree=0.34, seed=5)
        m.fit_external(it, num_col=6)
        B = m.param.n_bins
        for tree in m.trees:                 # ≤ ⌈0.34·6⌉ = 3 features/tree
            used = set()
            for level in range(tree["feat"].shape[0]):
                n_nodes = 1 << level
                feat = tree["feat"][level][:n_nodes]
                thr = tree["thr"][level][:n_nodes]
                used.update(np.asarray(feat)[np.asarray(thr) < B - 1].tolist())
            assert len(used) <= 3, used


class TestMulticlass:
    def _data(self, n=6000, F=6, K=3, seed=0):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, F)).astype(np.float32)
        # separable blobs along features 0/1 — centers FIXED across calls
        # so train/validation draws come from the same distribution
        centers = np.random.default_rng(42).normal(scale=3.0, size=(K, 2))
        y = rng.integers(0, K, n)
        X[:, :2] += centers[y]
        return X, y.astype(np.float32)

    def test_train_predict(self):
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data()
        m = HistGBT(n_trees=15, max_depth=4, n_bins=32,
                    objective="multi:softmax", num_class=3,
                    learning_rate=0.5)
        m.fit(X, y)
        pred = m.predict(X)
        assert pred.shape == (len(y),)
        acc = (pred == y).mean()
        assert acc > 0.9, acc
        proba = m.predict_proba(X)
        assert proba.shape == (len(y), 3)
        np.testing.assert_allclose(proba.sum(1), 1.0, atol=1e-5)
        assert (proba.argmax(1) == pred).all()

    @pytest.mark.slow
    def test_save_load_and_continue(self, tmp_path):
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data(n=3000)
        m = HistGBT(n_trees=6, max_depth=3, n_bins=32,
                    objective="multi:softmax", num_class=3)
        m.fit(X, y)
        uri = str(tmp_path / "mc.bin")
        m.save_model(uri)
        m2 = HistGBT.load_model(uri)
        np.testing.assert_array_equal(m2.predict(X), m.predict(X))
        m2.param.init({"n_trees": 4})
        m2.fit(X, y)                         # continue training
        assert len(m2.trees) == 10
        acc = (m2.predict(X) == y).mean()
        assert acc > 0.85, acc

    def test_early_stopping_multiclass(self):
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data(n=3000)
        Xv, yv = self._data(n=1500, seed=5)
        m = HistGBT(n_trees=100, max_depth=3, n_bins=32,
                    objective="multi:softmax", num_class=3,
                    learning_rate=0.5)
        m.fit(X, y, eval_set=(Xv, yv), early_stopping_rounds=10)
        assert m.best_iteration is not None

    def test_num_class_objective_consistency(self):
        import pytest
        from dmlc_core_tpu.base.logging import Error
        from dmlc_core_tpu.models import HistGBT

        # num_class left unset under multi:* is learned from the labels
        # (as XGBClassifier does) where make_device_data / fit scan them
        m = HistGBT(objective="multi:softmax", n_trees=1, max_depth=2,
                    n_bins=16)
        assert m.param.num_class == 1                    # not yet known
        X, y = self._data(n=500)
        m.fit(X, y)
        assert m.param.num_class == 3 and m.trees[0]["leaf"].shape[0] == 3
        with pytest.raises(Error):                       # one class only
            HistGBT(objective="multi:softmax").make_device_data(
                X, np.zeros(len(y), np.float32))
        with pytest.raises(Error):
            HistGBT(num_class=3)                         # objective not multi

    @pytest.mark.slow
    def test_sharded_equals_replicated_multiclass(self):
        from dmlc_core_tpu.models import HistGBT
        from dmlc_core_tpu.parallel.mesh import local_mesh

        X, y = self._data(n=1024, F=5)
        m8 = HistGBT(n_trees=4, max_depth=3, n_bins=32, mesh=local_mesh(),
                     objective="multi:softmax", num_class=3)
        m1 = HistGBT(n_trees=4, max_depth=3, n_bins=32, mesh=local_mesh(1),
                     objective="multi:softmax", num_class=3)
        m8.fit(X, y)
        m1.fit(X, y)
        for t8, t1 in zip(m8.trees, m1.trees):
            np.testing.assert_array_equal(t8["feat"], t1["feat"])
            np.testing.assert_array_equal(t8["thr"], t1["thr"])
            np.testing.assert_allclose(t8["leaf"], t1["leaf"],
                                       rtol=1e-4, atol=1e-5)

    @pytest.mark.slow
    def test_continue_then_early_stop_offsets_best_iteration(self, tmp_path):
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data(n=3000)
        Xv, yv = self._data(n=1500, seed=5)
        m = HistGBT(n_trees=6, max_depth=3, n_bins=32,
                    objective="multi:softmax", num_class=3)
        m.fit(X, y)
        uri = str(tmp_path / "c.bin")
        m.save_model(uri)
        m2 = HistGBT.load_model(uri)
        m2.param.init({"n_trees": 50, "learning_rate": 0.5})
        m2.fit(X, y, eval_set=(Xv, yv), early_stopping_rounds=10)
        # best_iteration must index into the COMBINED tree list (≥ priors)
        assert m2.best_iteration is not None and m2.best_iteration >= 6
        pd = m2.predict(Xv)
        acc = (pd == yv).mean()
        assert acc > 0.85, acc          # old trees not dropped

    def test_bad_labels_rejected(self):
        import pytest
        from dmlc_core_tpu.base.logging import Error
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data(n=500)
        y[0] = 3.0                      # out of [0, 3)
        m = HistGBT(n_trees=2, max_depth=2, n_bins=16,
                    objective="multi:softmax", num_class=3)
        with pytest.raises(Error):
            m.fit(X, y)

    def test_predict_proba_rejects_regression(self):
        import pytest
        from dmlc_core_tpu.base.logging import Error
        from dmlc_core_tpu.models import HistGBT

        rng = np.random.default_rng(0)
        X = rng.normal(size=(500, 4)).astype(np.float32)
        m = HistGBT(n_trees=2, max_depth=2, n_bins=16,
                    objective="reg:squarederror")
        m.fit(X, X[:, 0])
        with pytest.raises(Error):
            m.predict_proba(X)


class TestEvalMetrics:
    def _data(self, n, seed):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, 6)).astype(np.float32)
        y = (X[:, 0] * X[:, 1] + 0.5 * X[:, 2] > 0).astype(np.float32)
        return X, y

    def test_auc_early_stopping_maximizes(self):
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data(4000, 0)
        Xv, yv = self._data(2000, 9)
        m = HistGBT(n_trees=150, max_depth=3, n_bins=32, learning_rate=0.5,
                    eval_metric="auc")
        m.fit(X, y, eval_set=(Xv, yv), early_stopping_rounds=10)
        assert m.best_score is not None and 0.9 < m.best_score <= 1.0

    def test_auc_matches_sklearn_style_oracle(self):
        from dmlc_core_tpu.models.histgbt import _metric_auc
        import jax.numpy as jnp

        rng = np.random.default_rng(3)
        y = (rng.random(500) > 0.5).astype(np.float32)
        s = rng.normal(size=500).astype(np.float32) + y  # informative score
        # O(n^2) oracle: P(score_pos > score_neg)
        pos = s[y == 1][:, None]
        neg = s[y == 0][None, :]
        want = (pos > neg).mean() + 0.5 * (pos == neg).mean()
        got = float(_metric_auc(jnp.asarray(s), jnp.asarray(y)))
        assert abs(got - want) < 1e-3, (got, want)

    def test_error_metric(self):
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data(3000, 1)
        Xv, yv = self._data(1000, 2)
        m = HistGBT(n_trees=30, max_depth=3, n_bins=32, eval_metric="error")
        m.fit(X, y, eval_set=(Xv, yv))
        assert m.best_score is not None and m.best_score < 0.1

    def test_auc_midranks_on_ties(self):
        from dmlc_core_tpu.models.histgbt import _metric_auc
        import jax.numpy as jnp

        # all-tied margins must give exactly 0.5 regardless of label order
        y = np.array([1, 1, 1, 0, 0, 0], np.float32)
        s = np.zeros(6, np.float32)
        assert float(_metric_auc(jnp.asarray(s), jnp.asarray(y))) == 0.5
        # single-class validation set: neutral 0.5, not NaN
        y1 = np.ones(6, np.float32)
        assert float(_metric_auc(jnp.asarray(s), jnp.asarray(y1))) == 0.5

    def test_eval_metric_objective_mismatch_rejected(self):
        import pytest
        from dmlc_core_tpu.base.logging import Error
        from dmlc_core_tpu.models import HistGBT

        with pytest.raises(Error):
            HistGBT(eval_metric="merror")          # binary obj, multi metric
        with pytest.raises(Error):
            HistGBT(objective="reg:squarederror", eval_metric="auc")


def test_gain_importance():
    X, y = _synthetic(n=4000, f=6)
    m = HistGBT(n_trees=12, max_depth=4, n_bins=32, learning_rate=0.5)
    m.fit(X, y)
    w = m.feature_importances("weight")
    g = m.feature_importances("gain")
    assert g.shape == (6,)
    assert (g >= 0).all() and g.sum() > 0
    # informative features (0..3 in _synthetic's margin) dominate by gain
    assert g[:4].sum() > g[4:].sum()
    # trees carry gains; weight importance unchanged by the addition
    assert all("gain" in t for t in m.trees)
    assert w.sum() > 0


def test_gain_importance_multiclass(tmp_path):
    rng = np.random.default_rng(0)
    K = 3
    centers = np.random.default_rng(42).normal(scale=3.0, size=(K, 2))
    yl = rng.integers(0, K, 3000)
    X = rng.normal(size=(3000, 5)).astype(np.float32)
    X[:, :2] += centers[yl]
    m = HistGBT(n_trees=6, max_depth=3, n_bins=32,
                objective="multi:softmax", num_class=K)
    m.fit(X, yl.astype(np.float32))
    g = m.feature_importances("gain")
    assert g[:2].sum() > g[2:].sum()
    # survives save/load
    uri = str(tmp_path / "g.bin")
    m.save_model(uri)
    g2 = HistGBT.load_model(uri).feature_importances("gain")
    np.testing.assert_allclose(g2, g)


def test_predict_batching_consistent(monkeypatch):
    X, y = _synthetic(n=5000, f=5)
    m = HistGBT(n_trees=5, max_depth=3, n_bins=32)
    m.fit(X, y)
    whole = m.predict(X, output_margin=True)
    monkeypatch.setattr(HistGBT, "_PREDICT_BATCH", 1234)  # force 5 batches
    batched = m.predict(X, output_margin=True)
    np.testing.assert_array_equal(whole, batched)


def test_predict_empty_input():
    X, y = _synthetic(n=500, f=4)
    m = HistGBT(n_trees=2, max_depth=2, n_bins=16)
    m.fit(X, y)
    assert m.predict(np.zeros((0, 4), np.float32)).shape == (0,)


class TestMonotoneConstraints:
    def _data(self, n=6000, seed=0):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, 4)).astype(np.float32)
        # true relationship increasing in x0 but with noise that tempts
        # locally-decreasing splits; x1 genuinely non-monotone
        y = (X[:, 0] + np.sin(3 * X[:, 1]) +
             0.5 * rng.normal(size=n)).astype(np.float32)
        return X, y

    def _sweep_margins(self, m, X, feature, n_grid=64):
        """Margins along a grid of one feature, others at fixed rows."""
        base = X[:50].copy()
        grid = np.linspace(X[:, feature].min(), X[:, feature].max(), n_grid)
        out = np.empty((50, n_grid), np.float32)
        for j, v in enumerate(grid):
            Xs = base.copy()
            Xs[:, feature] = v
            out[:, j] = m.predict(Xs, output_margin=True)
        return out

    @pytest.mark.slow
    def test_increasing_constraint_enforced(self):
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data()
        m = HistGBT(n_trees=25, max_depth=4, n_bins=64, learning_rate=0.3,
                    objective="reg:squarederror",
                    monotone_constraints=[1, 0, 0, 0])
        m.fit(X, y)
        sweep = self._sweep_margins(m, X, 0)
        diffs = np.diff(sweep, axis=1)
        assert (diffs >= -1e-5).all(), diffs.min()   # globally non-decreasing
        # and the model still fits: rmse clearly better than predicting mean
        rmse = np.sqrt(np.mean((m.predict(X) - y) ** 2))
        assert rmse < np.std(y) * 0.8, rmse

    def test_unconstrained_would_violate(self):
        """Sanity: without the constraint the same data produces local
        decreases along x0 (so the previous test is non-vacuous)."""
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data()
        m = HistGBT(n_trees=25, max_depth=4, n_bins=64, learning_rate=0.3,
                    objective="reg:squarederror")
        m.fit(X, y)
        sweep = self._sweep_margins(m, X, 0)
        assert (np.diff(sweep, axis=1) < -1e-4).any()

    def test_decreasing_constraint(self):
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data()
        m = HistGBT(n_trees=15, max_depth=3, n_bins=32, learning_rate=0.3,
                    objective="reg:squarederror",
                    monotone_constraints=[0, 0, 0, -1])
        m.fit(X, y)
        sweep = self._sweep_margins(m, X, 3)
        assert (np.diff(sweep, axis=1) <= 1e-5).all()

    def test_no_constraints_trees_unchanged(self):
        """monotone_constraints of all zeros must not change training."""
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data(n=2000)
        a = HistGBT(n_trees=5, max_depth=3, n_bins=32,
                    objective="reg:squarederror")
        b = HistGBT(n_trees=5, max_depth=3, n_bins=32,
                    objective="reg:squarederror",
                    monotone_constraints=[0, 0, 0, 0])
        a.fit(X, y)
        b.fit(X, y, cuts=a.cuts)
        for ta, tb in zip(a.trees, b.trees):
            np.testing.assert_array_equal(ta["feat"], tb["feat"])
            np.testing.assert_array_equal(ta["thr"], tb["thr"])

    def test_bad_constraints_rejected(self):
        import pytest
        from dmlc_core_tpu.base.logging import Error
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data(n=500)
        m = HistGBT(n_trees=2, max_depth=2, n_bins=16,
                    objective="reg:squarederror",
                    monotone_constraints=[1, 0])       # wrong length
        with pytest.raises(Error):
            m.fit(X, y)

    def test_noninteger_constraints_rejected(self):
        import pytest
        from dmlc_core_tpu.base.logging import Error
        from dmlc_core_tpu.models import HistGBT

        X, y = self._data(n=500)
        m = HistGBT(n_trees=2, max_depth=2, n_bins=16,
                    objective="reg:squarederror",
                    monotone_constraints=[0.5, 0, 0, 0])
        with pytest.raises(Error):
            m.fit(X, y)


class TestRoundProgramCache:
    """The process-wide compiled-round-program cache
    (histgbt._ROUND_FN_CACHE) must share programs across instances
    without leaking one instance's live param mutations into another's
    cached program."""

    def test_identical_config_shares_program_and_trees(self):
        from dmlc_core_tpu.models import HistGBT
        from dmlc_core_tpu.models import histgbt as hg

        X, y = _synthetic(n=1024, f=6, seed=11)
        m1 = HistGBT(n_trees=4, max_depth=3, n_bins=32)
        m1.fit(X, y)
        key = m1._round_fn_cache_key(m1._round_plan(6), 4)
        assert key in hg._ROUND_FN_CACHE
        m2 = HistGBT(n_trees=4, max_depth=3, n_bins=32)
        m2.fit(X, y)
        assert m1._round_fn is m2._round_fn
        for a, b in zip(m1.trees, m2.trees):
            np.testing.assert_array_equal(a["feat"], b["feat"])
            np.testing.assert_allclose(a["leaf"], b["leaf"], rtol=1e-6)

    def test_param_mutation_does_not_poison_cache(self):
        """Mutating instance A's param AFTER its fit must not change
        what a fresh same-config instance B trains with — the cached
        program snapshots every param at build time, and a RETRACE at a
        new input shape must not re-read A's live (mutated) values."""
        from dmlc_core_tpu.models import HistGBT

        X, y = _synthetic(n=1024, f=6, seed=12)
        a = HistGBT(n_trees=3, max_depth=2, n_bins=16, subsample=0.8)
        a.fit(X, y)
        a.param.subsample = 0.1          # hostile live mutation
        b = HistGBT(n_trees=3, max_depth=2, n_bins=16, subsample=0.8)
        # different row count -> padded shape differs -> jax retraces
        # the cached closure; the retrace must see 0.8, not A's 0.1
        X2, y2 = _synthetic(n=1360, f=6, seed=12)
        b.fit(X2, y2)
        # oracle: same fit through a CLEAN cache (a poisoned retrace
        # would have trained b with 0.1 — comparing b against another
        # hit of the same cached program would hide that)
        from dmlc_core_tpu.models import histgbt as hg
        hg._ROUND_FN_CACHE.clear()
        c = HistGBT(n_trees=3, max_depth=2, n_bins=16, subsample=0.8)
        c.fit(X2, y2)
        for tb, tc in zip(b.trees, c.trees):
            np.testing.assert_array_equal(tb["feat"], tc["feat"])
            np.testing.assert_allclose(tb["leaf"], tc["leaf"], rtol=1e-6)


class TestMissingValues:
    """NaN-as-missing with LEARNED default direction (XGBoost
    semantics).  The oracle is MNAR masking: a feature is masked
    exactly where its value was positive, so only a model that routes
    missing rows to the learned side can recover the signal — aliasing
    NaN into an extreme bin (the pre-feature behavior) or any fixed
    direction caps masked-row accuracy near chance."""

    @staticmethod
    def _mnar_problem(n=1500, seed=0):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, 6)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        Xm = X.copy()
        mask = X[:, 0] > 0
        Xm[mask, 0] = np.nan
        return X, Xm, y, mask

    def test_learned_direction_recovers_mnar_signal(self):
        from dmlc_core_tpu.models import HistGBT

        _, Xm, y, mask = self._mnar_problem()
        m = HistGBT(n_trees=10, max_depth=4, n_bins=64)
        m.fit(Xm, y)
        assert m._missing and "dir" in m.trees[0]
        pred = m.predict(Xm) > 0.5
        assert (pred == y).mean() > 0.95
        assert (pred[mask] == y[mask]).mean() > 0.95   # the masked rows

    def test_nan_free_data_unchanged(self):
        """No NaN -> no missing mode, no dir arrays: the default path
        (and its compiled program) is byte-identical to before."""
        from dmlc_core_tpu.models import HistGBT

        X, _, y, _ = self._mnar_problem()
        m = HistGBT(n_trees=5, max_depth=3, n_bins=32)
        m.fit(X, y)
        assert not m._missing
        assert "dir" not in m.trees[0]

    def test_sharded_equals_replicated_with_nan(self):
        """DP-correctness oracle extended to missing mode: the psum'd
        histograms carry the missing-bin mass, so the 8-device mesh must
        choose identical splits AND directions as 1 device."""
        from dmlc_core_tpu.models import HistGBT

        _, Xm, y, _ = self._mnar_problem(n=1024, seed=3)
        m8 = HistGBT(n_trees=5, max_depth=3, n_bins=32, mesh=local_mesh())
        m1 = HistGBT(n_trees=5, max_depth=3, n_bins=32,
                     mesh=local_mesh(1))
        m8.fit(Xm, y)
        m1.fit(Xm, y)
        for t8, t1 in zip(m8.trees, m1.trees):
            np.testing.assert_array_equal(t8["feat"], t1["feat"])
            np.testing.assert_array_equal(t8["thr"], t1["thr"])
            np.testing.assert_array_equal(t8["dir"], t1["dir"])

    def test_nan_rejected_on_non_missing_model(self):
        import pytest
        from dmlc_core_tpu.base.logging import Error
        from dmlc_core_tpu.models import HistGBT

        X, Xm, y, _ = self._mnar_problem(n=800)
        m = HistGBT(n_trees=3, max_depth=3, n_bins=32)
        m.fit(X, y)                       # NaN-free fit
        with pytest.raises(Error):
            m.predict(Xm)
        with pytest.raises(Error):
            m.fit(Xm, y)                  # continued fit with NaN

    def test_eval_set_early_stopping_with_nan(self):
        from dmlc_core_tpu.models import HistGBT

        _, Xm, y, _ = self._mnar_problem(n=1200, seed=5)
        m = HistGBT(n_trees=10, max_depth=3, n_bins=32,
                    eval_metric="logloss")
        m.fit(Xm[:900], y[:900], eval_set=(Xm[900:], y[900:]),
              early_stopping_rounds=5)
        assert m.best_score is not None

    def test_multiclass_with_nan(self):
        from dmlc_core_tpu.models import HistGBT

        rng = np.random.default_rng(7)
        X = rng.normal(size=(800, 5)).astype(np.float32)
        y = np.clip(np.digitize(X[:, 0], [-0.5, 0.5]), 0, 2).astype(
            np.float32)
        Xm = X.copy()
        Xm[X[:, 0] > 0.5, 0] = np.nan     # masks exactly class 2
        m = HistGBT(n_trees=5, max_depth=3, n_bins=32,
                    objective="multi:softmax", num_class=3)
        m.fit(Xm, y)
        acc = (m.predict(Xm) == y).mean()
        assert acc > 0.9, acc

    def test_dump_save_load_roundtrip(self, tmp_path):
        from dmlc_core_tpu.models import HistGBT

        _, Xm, y, _ = self._mnar_problem(n=800, seed=9)
        m = HistGBT(n_trees=4, max_depth=3, n_bins=32)
        m.fit(Xm, y)
        assert "missing=" in m.dump_model()
        uri = str(tmp_path / "miss.ckpt")
        m.save_model(uri)
        m2 = HistGBT.load_model(uri)
        assert m2._missing
        np.testing.assert_allclose(m2.predict(Xm), m.predict(Xm),
                                   rtol=1e-6)
        leaves = m2.predict_leaf(Xm[:64])
        assert leaves.shape == (64, 4)

    def test_external_memory_rejects_nan(self, tmp_path):
        import pytest
        from dmlc_core_tpu.base.logging import Error
        from dmlc_core_tpu.data.iter import RowBlockIter
        from dmlc_core_tpu.models import HistGBT

        path = tmp_path / "nan.libsvm"
        with open(path, "w") as f:
            f.write("1 0:nan 1:2.0\n0 0:1.0 1:3.0\n")
        it = RowBlockIter.create(str(path), 0, 1, "libsvm")
        m = HistGBT(n_trees=2, max_depth=2, n_bins=16)
        with pytest.raises(Error):
            m.fit_external(it, num_col=2)
        # explicit cuts= skips the sketch pass — the page-binning pass
        # must still reject NaN (it would otherwise silently alias into
        # the top value bin)
        cuts = jnp.asarray(np.tile(np.linspace(-1, 1, 15,
                                               dtype=np.float32), (2, 1)))
        it2 = RowBlockIter.create(str(path), 0, 1, "libsvm")
        m2 = HistGBT(n_trees=2, max_depth=2, n_bins=16)
        with pytest.raises(Error):
            m2.fit_external(it2, num_col=2, cuts=cuts)

    def test_sticky_missing_model_rejects_fit_external(self, tmp_path):
        import pytest
        from dmlc_core_tpu.base.logging import Error
        from dmlc_core_tpu.data.iter import RowBlockIter
        from dmlc_core_tpu.models import HistGBT

        _, Xm, y, _ = self._mnar_problem(n=600, seed=11)
        m = HistGBT(n_trees=2, max_depth=2, n_bins=16)
        m.fit(Xm, y)                      # missing mode now sticky
        path = tmp_path / "clean.libsvm"
        with open(path, "w") as f:
            f.write("1 0:1.0\n0 0:2.0\n")
        it = RowBlockIter.create(str(path), 0, 1, "libsvm")
        with pytest.raises(Error):        # standard cuts would misread
            m.fit_external(it, num_col=1)  # the top value bin as missing

    def test_cuts_width_validated_against_mode(self):
        import pytest
        from dmlc_core_tpu.base.logging import Error
        from dmlc_core_tpu.models import HistGBT

        X, Xm, y, _ = self._mnar_problem(n=600, seed=13)
        m = HistGBT(n_trees=2, max_depth=2, n_bins=16)
        m.fit(Xm, y)
        m.trees.clear()                   # force the fresh-fit path
        # standard-width cuts [F, n_bins-1] into a missing-mode model
        # must fail loudly (the NaN bin would fall outside the histogram)
        bad = np.sort(np.random.default_rng(0).normal(
            size=(X.shape[1], 15)).astype(np.float32), axis=1)
        with pytest.raises(Error):
            m.fit(Xm, y, cuts=jnp.asarray(bad))



    def test_missing_with_sampling(self):
        """Missing mode composes with subsample/colsample (the sampled
        round program threads dir through the scan carry)."""
        from dmlc_core_tpu.models import HistGBT

        _, Xm, y, mask = self._mnar_problem(n=1500, seed=21)
        m = HistGBT(n_trees=10, max_depth=3, n_bins=32,
                    subsample=0.8, colsample_bytree=0.8, seed=3)
        m.fit(Xm, y)
        assert m._missing and "dir" in m.trees[0]
        pred = m.predict(Xm) > 0.5
        assert (pred[mask] == y[mask]).mean() > 0.85
        # deterministic across cached instances (same seed)
        m2 = HistGBT(n_trees=10, max_depth=3, n_bins=32,
                     subsample=0.8, colsample_bytree=0.8, seed=3)
        m2.fit(Xm, y)
        for a, b in zip(m.trees, m2.trees):
            np.testing.assert_array_equal(a["feat"], b["feat"])
            np.testing.assert_array_equal(a["dir"], b["dir"])


class TestRegAlpha:
    """reg_alpha (XGBoost L1 on leaf weights): gradient sums are
    soft-thresholded before weights and gains — ThresholdL1(G, a) =
    sign(G) * max(|G| - a, 0)."""

    def test_leaf_weights_shrink_toward_zero(self):
        X, y = _synthetic(n=2000, f=5, seed=17)
        base = HistGBT(n_trees=5, max_depth=3, n_bins=32)
        base.fit(X, y)
        l1 = HistGBT(n_trees=5, max_depth=3, n_bins=32, reg_alpha=2.0)
        l1.fit(X, y)
        m0 = np.mean([np.abs(t["leaf"]).mean() for t in base.trees])
        m1 = np.mean([np.abs(t["leaf"]).mean() for t in l1.trees])
        assert m1 < m0, (m1, m0)
        # huge alpha kills every leaf: |G| can never exceed it
        dead = HistGBT(n_trees=2, max_depth=3, n_bins=32,
                       reg_alpha=1e9)
        dead.fit(X, y)
        for t in dead.trees:
            np.testing.assert_allclose(t["leaf"], 0.0, atol=1e-7)

    def test_first_tree_root_leaf_matches_formula(self):
        """Depth-1 single tree: the two leaf weights must equal
        -eta * T(G_child, a) / (H_child + lam) computed by hand from
        the logistic gradients at the base margin."""
        rng = np.random.default_rng(23)
        X = rng.normal(size=(4096, 3)).astype(np.float32)
        y = (X[:, 0] > 0.2).astype(np.float32)
        a, lam, eta = 5.0, 1.0, 1.0
        m = HistGBT(n_trees=1, max_depth=1, n_bins=32, learning_rate=eta,
                    reg_lambda=lam, reg_alpha=a)
        m.fit(X, y)
        # logistic grads at margin 0: g = 0.5 - y, h = 0.25
        g = 0.5 - y
        h = np.full_like(y, 0.25)
        feat = int(m.trees[0]["feat"][0][0])
        thr = int(m.trees[0]["thr"][0][0])
        cuts = np.asarray(m.cuts)
        bins = np.searchsorted(cuts[feat], X[:, feat], side="right")
        left = bins <= thr
        def w(mask):
            G, H = g[mask].sum(), h[mask].sum()
            T = np.sign(G) * max(abs(G) - a, 0.0)
            return -eta * T / (H + lam)
        np.testing.assert_allclose(
            m.trees[0]["leaf"], [w(left), w(~left)], rtol=2e-3, atol=1e-4)

    def test_external_chunked_applies_alpha(self, tmp_path, monkeypatch):
        from dmlc_core_tpu.data.iter import RowBlockIter

        X, y = _synthetic(n=1500, f=4, seed=19)
        path = tmp_path / "a.libsvm"
        with open(path, "w") as f:
            for i in range(len(y)):
                feats = " ".join(f"{j}:{X[i, j]:.6f}" for j in range(4))
                f.write(f"{int(y[i])} {feats}\n")
        monkeypatch.setenv("DMLC_TPU_EXTERNAL_DEVICE_BUDGET", "40000")
        e0 = HistGBT(n_trees=3, max_depth=3, n_bins=16)
        e0.fit_external(
            RowBlockIter.create(str(path), 0, 1, "libsvm"), num_col=4)
        e1 = HistGBT(n_trees=3, max_depth=3, n_bins=16, reg_alpha=3.0)
        e1.fit_external(
            RowBlockIter.create(str(path), 0, 1, "libsvm"), num_col=4)
        m0 = np.mean([np.abs(t["leaf"]).mean() for t in e0.trees])
        m1 = np.mean([np.abs(t["leaf"]).mean() for t in e1.trees])
        assert m1 < m0, (m1, m0)

    def test_mono_plus_alpha_rejected(self):
        import pytest as pt
        from dmlc_core_tpu.base.logging import Error

        X, y = _synthetic(n=512, f=4, seed=3)
        m = HistGBT(n_trees=2, max_depth=2, n_bins=16, reg_alpha=0.5,
                    monotone_constraints=[1, 0, 0, 0])
        with pt.raises(Error):
            m.fit(X, y)


class TestScalePosWeight:
    """scale_pos_weight (XGBoost's imbalanced-data knob): positives'
    grad/hess scale by the factor — definitionally an instance weight,
    so the exactness oracle is tree-for-tree equality with an explicit
    weight vector."""

    @staticmethod
    def _imbalanced(n=2000, pos_frac=0.05, seed=0):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, 5)).astype(np.float32)
        y = (X[:, 0] > np.quantile(X[:, 0], 1 - pos_frac)).astype(
            np.float32)
        return X, y

    def test_equals_explicit_weights_exactly(self):
        X, y = self._imbalanced()
        spw = float((y == 0).sum() / (y == 1).sum())
        a = HistGBT(n_trees=5, max_depth=3, n_bins=32,
                    scale_pos_weight=spw)
        a.fit(X, y)
        b = HistGBT(n_trees=5, max_depth=3, n_bins=32)
        b.fit(X, y, weight=np.where(y == 1.0, np.float32(spw),
                                    np.float32(1.0)))
        for ta, tb in zip(a.trees, b.trees):
            np.testing.assert_array_equal(ta["feat"], tb["feat"])
            np.testing.assert_array_equal(ta["thr"], tb["thr"])
            np.testing.assert_allclose(ta["leaf"], tb["leaf"], rtol=1e-6)

    def test_fit_device_path_applies_it(self):
        """The make_device_data -> fit_device handle path must honor the
        knob too (it builds w_d itself)."""
        X, y = self._imbalanced(n=1200, seed=4)
        spw = 20.0
        a = HistGBT(n_trees=4, max_depth=3, n_bins=32,
                    scale_pos_weight=spw)
        dd = a.make_device_data(X, y)
        a.fit_device(dd)
        b = HistGBT(n_trees=4, max_depth=3, n_bins=32,
                    scale_pos_weight=spw)
        b.fit(X, y)
        for ta, tb in zip(a.trees, b.trees):
            np.testing.assert_array_equal(ta["feat"], tb["feat"])
            np.testing.assert_allclose(ta["leaf"], tb["leaf"], rtol=1e-6)

    def test_external_memory_matches_explicit_weights(self, tmp_path):
        """The streaming path's cuts AND trees must match the explicit
        weight vector equivalent (sketch pass sees scaled weights)."""
        from dmlc_core_tpu.data.iter import RowBlockIter

        X, y = self._imbalanced(n=600, seed=6)
        spw = 10.0
        path = tmp_path / "imb.libsvm"
        with open(path, "w") as f:
            for i in range(len(y)):
                feats = " ".join(f"{j}:{X[i, j]:.6f}" for j in range(5))
                f.write(f"{int(y[i])} {feats}\n")
        a = HistGBT(n_trees=4, max_depth=3, n_bins=32,
                    scale_pos_weight=spw)
        a.fit_external(RowBlockIter.create(str(path), 0, 1, "libsvm"),
                       num_col=5)
        b = HistGBT(n_trees=4, max_depth=3, n_bins=32)
        b.fit(X, y, weight=np.where(y == 1.0, np.float32(spw),
                                    np.float32(1.0)))
        # cuts come from different estimators (streaming sketch vs
        # in-core quantiles) so trees can differ at boundaries; the
        # predictions must agree
        agree = ((a.predict(X) > 0.5) == (b.predict(X) > 0.5)).mean()
        assert agree > 0.97, agree

    def test_improves_recall_on_imbalanced(self):
        X, y = self._imbalanced(n=3000, pos_frac=0.03, seed=2)
        plain = HistGBT(n_trees=10, max_depth=3, n_bins=32)
        plain.fit(X, y)
        spw = HistGBT(n_trees=10, max_depth=3, n_bins=32,
                      scale_pos_weight=30.0)
        spw.fit(X, y)
        pos = y == 1
        rec_plain = ((plain.predict(X) > 0.5)[pos]).mean()
        rec_spw = ((spw.predict(X) > 0.5)[pos]).mean()
        assert rec_spw >= rec_plain
        assert rec_spw > 0.9, rec_spw

    def test_rejected_for_non_binary_objectives(self):
        import pytest
        from dmlc_core_tpu.base.logging import Error

        X, y = self._imbalanced(n=500)
        m = HistGBT(n_trees=2, max_depth=2, n_bins=16,
                    objective="reg:squarederror", scale_pos_weight=3.0)
        with pytest.raises(Error):
            m.fit(X, y)

    def test_sklearn_passthrough(self):
        from dmlc_core_tpu.models.sklearn import GBTClassifier

        X, y = self._imbalanced(n=1500)
        est = GBTClassifier(n_estimators=5, max_depth=3, n_bins=32,
                            scale_pos_weight=10.0)
        est.fit(X, y)
        assert est.model.param.scale_pos_weight == 10.0
        # GridSearchCV path: set_params must validate + route it
        est2 = GBTClassifier(n_estimators=2).set_params(
            scale_pos_weight=4.0)
        assert est2.get_params()["scale_pos_weight"] == 4.0
