"""Out-of-core training path (BASELINE config 3): streaming sketch +
external-memory hist-GBT over CSR pages.
"""

import os

import pytest
import numpy as np

from dmlc_core_tpu.io.filesystem import TemporaryDirectory
from dmlc_core_tpu.data.iter import RowBlockIter
from dmlc_core_tpu.models.histgbt import HistGBT
from dmlc_core_tpu.ops.quantile import (
    SketchAccumulator,
    compute_cuts,
)


def _synth(n, F, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 > 0.5).astype(np.float32)
    return X, y


def _rank_error(X, cuts, n_bins):
    """Max |empirical CDF at cut − target quantile| over features/cuts."""
    target = np.arange(1, n_bins) / n_bins
    errs = []
    for f in range(X.shape[1]):
        ecdf = np.searchsorted(np.sort(X[:, f]), cuts[f],
                               side="right") / len(X)
        errs.append(np.abs(ecdf - target))
    return float(np.max(errs))


def _write_libsvm(path, X, y):
    with open(path, "w") as f:
        for i in range(len(X)):
            feats = " ".join(f"{j}:{X[i, j]:.6f}" for j in range(X.shape[1]))
            f.write(f"{y[i]:.0f} {feats}\n")


def _weighted_rank_interval_error(x, w, cuts, n_bins):
    """Max distance from each cut's target rank to its achievable rank
    interval ``[P(X < c), P(X ≤ c)]``.

    Atoms (duplicated values) make the rank set-valued, so interval
    distance is the honest metric for discrete mass.  Cuts that
    merge_summaries ε-bumped apart to stay strictly increasing (a run of
    targets landing on one atom) are scored as ONE cluster at the run's
    first cut — the bumped copies route rows identically, so they are a
    representation detail, not sketch error."""
    order = np.argsort(x, kind="stable")
    xs, ws = x[order], w[order]
    cw = np.cumsum(ws)
    total = cw[-1]
    target = np.arange(1, n_bins) / n_bins
    err = 0.0
    rep = cuts[0]
    for q, c in zip(target, cuts):
        tol = max(abs(rep), 1.0) * 1e-6 * (n_bins + 1)
        if c - rep > tol:
            rep = c                       # genuinely new cut value
        lo = np.searchsorted(xs, rep, side="left")
        hi = np.searchsorted(xs, rep, side="right")
        r_lo = (cw[lo - 1] if lo > 0 else 0.0) / total
        r_hi = (cw[hi - 1] if hi > 0 else 0.0) / total
        if q < r_lo:
            err = max(err, r_lo - q)
        elif q > r_hi:
            err = max(err, q - r_hi)
    return err


def _sketch_eps(n_summary, pages, cap):
    """The documented bound from ops/quantile.py: (⌈log_C P⌉+4)/(S−1).
    Integer ladder depth — float log rounds exact powers of C up a level
    and would silently test a looser bound."""
    levels = 1
    while cap ** levels < max(pages, 2):
        levels += 1
    return (levels + 4) / (n_summary - 1)


class TestSketchErrorBound:
    """Adversarial-distribution property tests of the documented
    eps(S, P, C) rank-error bound (SURVEY.md §7 hard part (c): the
    reference world's GK sketches carry provable guarantees — so must
    the fixed-size replacement)."""

    N_BINS = 32
    S = 512
    CAP = 4          # tiny buffer → maximal ladder depth for the bound

    def _stream(self, x, w, pages):
        acc = SketchAccumulator(1, n_summary=self.S, buffer_pages=self.CAP)
        for xs, ws in zip(np.array_split(x, pages),
                          np.array_split(w, pages)):
            acc.add(xs.reshape(-1, 1), ws)
        cuts = np.asarray(acc.finalize(self.N_BINS))[0]
        bound = _sketch_eps(self.S, acc.pages_seen, self.CAP)
        err = _weighted_rank_interval_error(x, w, cuts, self.N_BINS)
        assert err <= bound, (err, bound)
        return err, bound

    def test_heavy_tail(self):
        rng = np.random.default_rng(10)
        x = rng.pareto(0.5, size=30_000).astype(np.float32)  # infinite mean
        self._stream(x, np.ones_like(x), pages=37)

    def test_lognormal_wide(self):
        rng = np.random.default_rng(11)
        x = np.exp(rng.normal(0, 6, size=30_000)).astype(np.float32)
        self._stream(x, np.ones_like(x), pages=29)

    def test_near_duplicate_atoms(self):
        rng = np.random.default_rng(12)
        x = np.full(30_000, 3.25, np.float32)       # 99.9% one atom
        idx = rng.choice(len(x), 30, replace=False)
        x[idx] = rng.normal(size=30).astype(np.float32)
        self._stream(x, np.ones_like(x), pages=23)

    def test_massive_weight_skew(self):
        rng = np.random.default_rng(13)
        x = rng.normal(size=20_000).astype(np.float32)
        w = np.full_like(x, 1e-6)
        w[x > 1.5] = 1e6                            # 10^12 dynamic range
        self._stream(x, w, pages=31)

    def test_sorted_stream_order(self):
        # pages arrive sorted: every page summarizes a disjoint value
        # range — the worst case for naive averaging of summaries
        rng = np.random.default_rng(14)
        x = np.sort(rng.normal(size=30_000).astype(np.float32))
        self._stream(x, np.ones_like(x), pages=41)

    def test_many_pages_log_growth(self):
        # 400 pages through a 4-ary ladder: the flat collapse-all design
        # would compound ~100 merge stages of error; the ladder stays
        # within the log-depth bound
        rng = np.random.default_rng(15)
        x = rng.normal(size=40_000).astype(np.float32)
        err, bound = self._stream(x, np.ones_like(x), pages=400)
        assert bound < 0.02, bound   # the bound itself stays tight


class TestSketchAccumulator:
    def test_streaming_matches_full(self):
        X, _ = _synth(20_000, 5)
        full_cuts = np.asarray(compute_cuts(X, n_bins=32))
        acc = SketchAccumulator(5, n_summary=512, buffer_pages=4)
        for page in np.array_split(X, 23):  # uneven pages force collapses
            acc.add(page)
        stream_cuts = np.asarray(acc.finalize(32))
        # the operative sketch metric: rank (quantile) error of each cut,
        # which must stay well below a bin width (1/32 ≈ 3.1%; XGBoost's
        # default sketch_eps is 3%)
        err = _rank_error(X, stream_cuts, 32)
        assert err < 0.01, err
        assert _rank_error(X, full_cuts, 32) < 0.002  # oracle sanity

    def test_bounded_memory(self):
        acc = SketchAccumulator(3, n_summary=64, buffer_pages=4)
        for _ in range(40):
            acc.add(np.random.default_rng(1).normal(size=(100, 3)))
        # C-ary ladder: ≤ C−1 summaries per level, O(log_C P) levels
        per_level = [len(lv) for lv in acc._levels]
        assert max(per_level) <= 3, per_level
        assert len(per_level) <= 4, per_level

    def test_weighted(self):
        rng = np.random.default_rng(2)
        x = rng.normal(size=(5_000, 1)).astype(np.float32)
        w = (x[:, 0] > 0).astype(np.float32) * 9 + 1  # positives weigh 10x
        acc = SketchAccumulator(1, n_summary=512, buffer_pages=8)
        for xs, ws in zip(np.array_split(x, 7), np.array_split(w, 7)):
            acc.add(xs, ws)
        cuts = np.asarray(acc.finalize(4))[0]  # 3 interior cuts
        # with positives outweighing 10:1, the weighted median is positive
        assert cuts[1] > 0

    def test_distributed_merge(self):
        X, _ = _synth(10_000, 3, seed=5)
        halves = [X[:5_000], X[5_000:]]
        summaries = []
        for h in halves:
            acc = SketchAccumulator(3, n_summary=512, buffer_pages=4)
            for page in np.array_split(h, 5):
                acc.add(page)
            summaries.append(acc)

        def fake_allgather(arr):
            # mimic collectives.allgather: stack rank values on axis 0
            if arr.ndim == 2:  # summary [F, S]
                return np.stack([summaries[0].summary()[0],
                                 summaries[1].summary()[0]])
            return np.asarray([summaries[0].summary()[1],
                               summaries[1].summary()[1]], np.float32)

        dist_cuts = np.asarray(summaries[0].finalize(16, fake_allgather))
        err = _rank_error(X, dist_cuts, 16)
        assert err < 0.015, err  # well under a bin width (1/16 ≈ 6.3%)


class TestFitExternal:
    def test_matches_in_core(self):
        """Same cuts + data → external page loop reproduces in-core trees."""
        X, y = _synth(4_000, 6, seed=3)
        with TemporaryDirectory() as tmp:
            data = os.path.join(tmp.path, "train.libsvm")
            cache = os.path.join(tmp.path, "cache")
            _write_libsvm(data, X, y)

            common = dict(n_trees=5, max_depth=3, n_bins=32,
                          hist_method="segment")
            incore = HistGBT(**common)
            incore.fit(X, y)

            it = RowBlockIter.create(f"{data}#{cache}", 0, 1, "libsvm")
            ext = HistGBT(**common)
            ext.fit_external(it, cuts=incore.cuts)
            it.close()

            for t_in, t_ext in zip(incore.trees, ext.trees):
                np.testing.assert_array_equal(t_in["feat"], t_ext["feat"])
                np.testing.assert_array_equal(t_in["thr"], t_ext["thr"])
                np.testing.assert_allclose(t_in["leaf"], t_ext["leaf"],
                                           rtol=2e-4, atol=2e-5)
            p_in = incore.predict(X[:256])
            p_ext = ext.predict(X[:256])
            np.testing.assert_allclose(p_in, p_ext, rtol=2e-3, atol=2e-4)

    def test_streaming_cuts_loss_decreases(self):
        X, y = _synth(3_000, 4, seed=9)
        with TemporaryDirectory() as tmp:
            data = os.path.join(tmp.path, "t.libsvm")
            _write_libsvm(data, X, y)
            it = RowBlockIter.create(data, 0, 1, "libsvm")
            m = HistGBT(n_trees=8, max_depth=3, n_bins=16,
                        hist_method="segment")
            m.fit_external(it)
            it.close()
            margins = m.predict(X, output_margin=True)
            # logloss of the trained model clearly beats the 0-margin start
            eps = 1e-7
            prob = 1 / (1 + np.exp(-margins))
            ll = -np.mean(y * np.log(prob + eps) + (1 - y) * np.log(1 - prob + eps))
            assert ll < 0.55, ll

    def test_multipage_cache(self):
        """Tiny page budget → many pages; results stay consistent."""
        X, y = _synth(2_000, 4, seed=11)
        with TemporaryDirectory() as tmp:
            data = os.path.join(tmp.path, "t.libsvm")
            cache = os.path.join(tmp.path, "c")
            _write_libsvm(data, X, y)
            from dmlc_core_tpu.data.iter import DiskRowIter
            from dmlc_core_tpu.data.parsers import Parser

            parser = Parser.create(data, 0, 1, "libsvm")
            parser.hint_chunk_size(8 << 10)  # small chunks → multiple pages
            it = DiskRowIter(parser, cache, page_bytes=16 << 10)
            assert it._num_pages > 3  # genuinely multi-page
            m = HistGBT(n_trees=3, max_depth=2, n_bins=16,
                        hist_method="segment")
            m.fit_external(it)
            it.close()
            assert len(m.trees) == 3


class TestChunkedStreamingEngine:
    """The over-budget path: pages stack into >1 fixed-shape chunks and
    stream per level (VERDICT r3 #3's O(depth·chunks) restructure).
    Small datasets normally auto-route to the cached engine, so these
    tests shrink DMLC_TPU_EXTERNAL_DEVICE_BUDGET until residency is
    impossible and the streaming engine must run."""

    def test_forced_chunked_matches_in_core(self, monkeypatch):
        X, y = _synth(4_000, 6, seed=3)
        # row state 4000·24 B; bins 4000·6 B — 110 kB forces ≥2 chunks
        monkeypatch.setenv("DMLC_TPU_EXTERNAL_DEVICE_BUDGET", "110000")
        with TemporaryDirectory() as tmp:
            data = os.path.join(tmp.path, "train.libsvm")
            cache = os.path.join(tmp.path, "cache")
            _write_libsvm(data, X, y)
            common = dict(n_trees=5, max_depth=3, n_bins=32,
                          hist_method="segment")
            incore = HistGBT(**common)
            incore.fit(X, y)
            it = RowBlockIter.create(f"{data}#{cache}", 0, 1, "libsvm")
            ext = HistGBT(**common)
            ext.fit_external(it, cuts=incore.cuts)
            it.close()
            for t_in, t_ext in zip(incore.trees, ext.trees):
                np.testing.assert_array_equal(t_in["feat"], t_ext["feat"])
                np.testing.assert_array_equal(t_in["thr"], t_ext["thr"])
                np.testing.assert_allclose(t_in["leaf"], t_ext["leaf"],
                                           rtol=2e-4, atol=2e-5)
            np.testing.assert_allclose(incore.predict(X[:256]),
                                       ext.predict(X[:256]),
                                       rtol=2e-3, atol=2e-4)

    @pytest.mark.slow
    def test_forced_chunked_multiclass(self, monkeypatch):
        rng = np.random.default_rng(5)
        X = rng.normal(size=(3_000, 5)).astype(np.float32)
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32) + (
            X[:, 2] > 0.8).astype(np.float32)
        # row state 3000·48 B = 144000; 150000 leaves 6000 B for bins →
        # 1200 rows/chunk → 3 chunks: genuinely multi-chunk multiclass
        monkeypatch.setenv("DMLC_TPU_EXTERNAL_DEVICE_BUDGET", "150000")
        with TemporaryDirectory() as tmp:
            data = os.path.join(tmp.path, "t.libsvm")
            _write_libsvm(data, X, y)
            common = dict(n_trees=4, max_depth=3, n_bins=16,
                          num_class=3, objective="multi:softmax",
                          hist_method="segment")
            incore = HistGBT(**common)
            incore.fit(X, y)
            it = RowBlockIter.create(data, 0, 1, "libsvm")
            ext = HistGBT(**common)
            ext.fit_external(it, num_col=5, cuts=incore.cuts)
            it.close()
            for t_in, t_ext in zip(incore.trees, ext.trees):
                np.testing.assert_array_equal(t_in["feat"], t_ext["feat"])
                np.testing.assert_array_equal(t_in["thr"], t_ext["thr"])
                np.testing.assert_allclose(t_in["leaf"], t_ext["leaf"],
                                           rtol=2e-4, atol=2e-5)
            assert (ext.predict(X) == incore.predict(X)).mean() > 0.99

    def test_forced_chunked_sampling_and_eval(self, monkeypatch, caplog):
        """Sampling + eval_every run through the streaming engine; draws
        are deterministic (two runs → identical trees) and training
        still learns."""
        X, y = _synth(3_000, 4, seed=9)
        # row state 3000·24 B = 72000; 80000 leaves 8000 B for bins →
        # 2000 rows/chunk → 2 chunks: the per-page keep-mask scatter
        # must spill across a chunk boundary
        monkeypatch.setenv("DMLC_TPU_EXTERNAL_DEVICE_BUDGET", "80000")
        runs = []
        for _ in range(2):
            with TemporaryDirectory() as tmp:
                data = os.path.join(tmp.path, "t.libsvm")
                _write_libsvm(data, X, y)
                it = RowBlockIter.create(data, 0, 1, "libsvm")
                m = HistGBT(n_trees=6, max_depth=3, n_bins=16, seed=7,
                            subsample=0.8, colsample_bytree=0.75,
                            hist_method="segment")
                m.fit_external(it, eval_every=3)
                it.close()
                runs.append(m)
        for ta, tb in zip(runs[0].trees, runs[1].trees):
            np.testing.assert_array_equal(ta["feat"], tb["feat"])
            np.testing.assert_array_equal(ta["thr"], tb["thr"])
            np.testing.assert_allclose(ta["leaf"], tb["leaf"],
                                       rtol=1e-5, atol=1e-6)
        margins = runs[0].predict(X, output_margin=True)
        prob = 1 / (1 + np.exp(-margins))
        eps = 1e-7
        ll = -np.mean(y * np.log(prob + eps)
                      + (1 - y) * np.log(1 - prob + eps))
        assert ll < 0.55, ll


class TestPredictIter:
    """Streaming inference: a model trained out-of-core must SCORE
    out-of-core — predictions over RowBlockIter pages must equal the
    dense predict, with host memory bounded by one staging slab."""

    def test_histgbt_matches_dense(self, tmp_path):
        X, y = _synth(3_000, 5, seed=21)
        m = HistGBT(n_trees=6, max_depth=3, n_bins=32,
                    hist_method="segment")
        m.fit(X, y)
        data = os.path.join(str(tmp_path), "p.libsvm")
        _write_libsvm(data, X, y)
        it = RowBlockIter.create(data, 0, 1, "libsvm")
        # tiny slab: forces many flushes and page-straddling slices
        got = m.predict_iter(it, batch_rows=257)
        it.close()
        # libsvm text round-trips at 6 decimals; the quantized bins are
        # almost always identical, but a value sitting exactly on a cut
        # may flip — compare through the text round-trip oracle
        X_rt = np.zeros_like(X)
        it = RowBlockIter.create(data, 0, 1, "libsvm")
        lo = 0
        for b in it:
            b.to_dense_into(X_rt[lo:lo + b.size])
            lo += b.size
        it.close()
        np.testing.assert_allclose(got, m.predict(X_rt),
                                   rtol=1e-6, atol=1e-7)
        # margins too
        it = RowBlockIter.create(data, 0, 1, "libsvm")
        gm = m.predict_iter(it, output_margin=True, batch_rows=1024)
        it.close()
        np.testing.assert_allclose(
            gm, m.predict(X_rt, output_margin=True), rtol=1e-6, atol=1e-7)

    def test_histgbt_feature_width_mismatch_fails(self, tmp_path):
        X, y = _synth(500, 3, seed=22)
        m = HistGBT(n_trees=2, max_depth=2, n_bins=16,
                    hist_method="segment")
        m.fit(X, y)
        wide, yw = _synth(100, 6, seed=23)
        data = os.path.join(str(tmp_path), "wide.libsvm")
        _write_libsvm(data, wide, yw)
        it = RowBlockIter.create(data, 0, 1, "libsvm")
        with pytest.raises(Exception, match="expects 3 features"):
            np.asarray(m.predict_iter(it))
        it.close()

    def test_gblinear_matches_dense(self, tmp_path):
        from dmlc_core_tpu.models.linear import GBLinear

        X, y = _synth(2_000, 4, seed=24)
        m = GBLinear(n_rounds=20, objective="binary:logistic")
        m.fit(X, y)
        data = os.path.join(str(tmp_path), "lp.libsvm")
        _write_libsvm(data, X, y)
        it = RowBlockIter.create(data, 0, 1, "libsvm")
        got = m.predict_iter(it, batch_rows=300)
        it.close()
        X_rt = np.zeros_like(X)
        it = RowBlockIter.create(data, 0, 1, "libsvm")
        lo = 0
        for b in it:
            b.to_dense_into(X_rt[lo:lo + b.size])
            lo += b.size
        it.close()
        np.testing.assert_allclose(got, m.predict(X_rt),
                                   rtol=1e-6, atol=1e-7)


@pytest.mark.slow
def test_external_memory_multiclass(tmp_path):
    """fit_external with multi:softmax must match in-core fit() given the
    same cuts (same data, single worker, deterministic splits)."""
    from dmlc_core_tpu.data.iter import RowBlockIter
    from dmlc_core_tpu.models import HistGBT

    rng = np.random.default_rng(0)
    K, n, F = 3, 3000, 6
    centers = np.random.default_rng(42).normal(scale=3.0, size=(K, 2))
    y = rng.integers(0, K, n)
    X = rng.normal(size=(n, F)).astype(np.float32)
    X[:, :2] += centers[y]

    svm = tmp_path / "mc.svm"
    _write_libsvm(svm, X, y)

    ext = HistGBT(n_trees=8, max_depth=3, n_bins=32,
                  objective="multi:softmax", num_class=K)
    it = RowBlockIter.create(str(svm), 0, 1, "libsvm")
    ext.fit_external(it, num_col=F)
    acc_ext = (ext.predict(X) == y).mean()
    assert acc_ext > 0.9, acc_ext

    core = HistGBT(n_trees=8, max_depth=3, n_bins=32,
                   objective="multi:softmax", num_class=K)
    core.fit(X, y.astype(np.float32), cuts=ext.cuts)
    for te, tc in zip(ext.trees, core.trees):
        np.testing.assert_array_equal(te["feat"], tc["feat"])
        np.testing.assert_array_equal(te["thr"], tc["thr"])
        np.testing.assert_allclose(te["leaf"], tc["leaf"],
                                   rtol=1e-3, atol=1e-4)


def test_host_pinned_passes_match_default(tmp_path, monkeypatch):
    """DMLC_TPU_SKETCH_BACKEND / DMLC_TPU_BIN_BACKEND pin the streaming
    passes to the host backend.  Same cuts, same trees as the default
    path."""
    from dmlc_core_tpu.data.iter import RowBlockIter
    from dmlc_core_tpu.models import HistGBT

    X, y = _synth(1500, 5)
    svm = tmp_path / "p.svm"
    _write_libsvm(svm, X, y)

    # conftest pins jax to CPU devices, so both branches compute on the
    # same backend and exact tree equality is deterministic (this test
    # checks the PINNING CODE PATH, not cross-backend float parity)
    models = {}
    for pinned in (False, True):
        if pinned:
            monkeypatch.setenv("DMLC_TPU_SKETCH_BACKEND", "cpu")
            monkeypatch.setenv("DMLC_TPU_BIN_BACKEND", "cpu")
        else:
            # ambient env (e.g. a bench_external debug session) must not
            # turn this into a vacuous pinned-vs-pinned comparison
            monkeypatch.delenv("DMLC_TPU_SKETCH_BACKEND", raising=False)
            monkeypatch.delenv("DMLC_TPU_BIN_BACKEND", raising=False)
        m = HistGBT(n_trees=4, max_depth=3, n_bins=32)
        it = RowBlockIter.create(str(svm), 0, 1, "libsvm")
        m.fit_external(it, num_col=5)
        it.close()
        models[pinned] = m
    np.testing.assert_allclose(np.asarray(models[True].cuts),
                               np.asarray(models[False].cuts),
                               rtol=1e-6)
    for t0, t1 in zip(models[False].trees, models[True].trees):
        np.testing.assert_array_equal(t0["feat"], t1["feat"])
        np.testing.assert_array_equal(t0["thr"], t1["thr"])
        np.testing.assert_allclose(t0["leaf"], t1["leaf"], rtol=1e-4)


def test_cache_device_matches_default(tmp_path):
    from dmlc_core_tpu.data.iter import RowBlockIter
    from dmlc_core_tpu.models import HistGBT

    X, y = _synth(2000, 5)
    svm = tmp_path / "c.svm"
    _write_libsvm(svm, X, y)

    models = {}
    for cache in (False, True):
        m = HistGBT(n_trees=5, max_depth=3, n_bins=32)
        it = RowBlockIter.create(str(svm), 0, 1, "libsvm")
        m.fit_external(it, num_col=5, cache_device=cache)
        it.close()
        models[cache] = m
    for t0, t1 in zip(models[False].trees, models[True].trees):
        np.testing.assert_array_equal(t0["feat"], t1["feat"])
        np.testing.assert_array_equal(t0["thr"], t1["thr"])
        # cache_device=True runs the in-core engine whose leaf sums come
        # from the histogram cumsum (histgbt precision note), not the
        # page loop's segment_sum — identical splits, ~1e-4 leaf drift
        np.testing.assert_allclose(t0["leaf"], t1["leaf"],
                                   rtol=1e-3, atol=1e-5)
    # post-fit contract parity with fit(): the cached path must leave
    # train_margins() usable (real rows only, padding sliced off)
    tm = models[True].train_margins()
    assert tm.shape[0] == len(y)
    np.testing.assert_allclose(
        tm, models[True].predict(X, output_margin=True), rtol=1e-4,
        atol=1e-5)
