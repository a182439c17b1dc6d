"""Cold-start pipeline tests: persistent compile cache wiring,
overlapped warmup correctness, streamed ingest parity, serve pre-warm.

The correctness bar everywhere is BIT-identity: the overlap/streaming
machinery is an optimization layered on the inline jit path, so any
divergence in trees, margins or eval curves is a bug, not noise.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from dmlc_core_tpu.base import compile_cache as cc
from dmlc_core_tpu.base import metrics as base_metrics
from dmlc_core_tpu.models import HistGBT
from dmlc_core_tpu.models.histgbt import (_AOT_EXEC_CACHE,
                                          _ROUND_FN_CACHE,
                                          _rounds_schedule)


def _tiny_fit(n_trees=2, depth=2, rows=160, feats=4, seed=0, **kw):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, feats)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] > 0).astype(np.float32)
    m = HistGBT(n_trees=n_trees, max_depth=depth, n_bins=8, **kw)
    m.fit(X, y, warmup_rounds=1)
    return m, X, y


def _trees(m):
    return [{k: np.asarray(v) for k, v in t.items()} for t in m.trees]


def _assert_same_trees(a, b):
    assert len(a) == len(b)
    for ta, tb in zip(a, b):
        assert set(ta) == set(tb)
        for k in ta:
            np.testing.assert_array_equal(ta[k], tb[k], err_msg=k)


@pytest.fixture
def tmp_cache(tmp_path):
    """Redirect the persistent cache to a fresh dir; restore the test
    harness's dir (conftest.py) afterwards so other tests keep their
    warm cache."""
    prev = jax.config.jax_compilation_cache_dir
    d = str(tmp_path / "xla_cache")
    cc.set_cache_dir(d)
    try:
        yield d
    finally:
        cc.set_cache_dir(prev)


class TestCompileCache:
    def test_dir_respected_and_hit_on_second_fit(self, tmp_cache):
        mark = cc.marker()
        _tiny_fit(rows=192)
        hits0, misses0 = cc.marker()
        # fresh dir: programs were compiled and WRITTEN there
        assert misses0 - mark[1] > 0
        assert os.path.isdir(tmp_cache) and len(os.listdir(tmp_cache)) > 0
        assert cc.stats()["dir"] == tmp_cache

        # drop every in-memory executable so the same-shape refit must
        # go back to XLA — which must now read the persistent cache
        jax.clear_caches()
        _ROUND_FN_CACHE.clear()
        _AOT_EXEC_CACHE.clear()
        _tiny_fit(rows=192)
        hits1, misses1 = cc.marker()
        assert hits1 - hits0 > 0, "second same-shape fit must hit"
        # and the hit/miss counters surface in the metrics registry
        reg = base_metrics.default_registry().snapshot()["metrics"]
        ev = reg.get("dmlc_compile_cache_events_total")
        if base_metrics.enabled():
            labels = {s["labels"]["event"] for s in ev["series"]}
            assert "hit" in labels and "miss" in labels

    def test_disabled_is_noop(self, monkeypatch):
        monkeypatch.setenv("DMLC_COMPILE_CACHE", "0")
        before = jax.config.jax_compilation_cache_dir
        assert cc.configure() is False
        assert cc.stats()["enabled"] is False
        assert jax.config.jax_compilation_cache_dir == before

    def test_verdict_classification(self):
        assert cc.verdict(cc.marker()) is None   # no traffic since mark

    def test_background_compile_failure_raises_at_join(self):
        # a program the compiler refuses stops the run with the
        # compiler's message — it is not retried down another path
        def refused():
            raise RuntimeError("Mosaic said no")

        bg = cc.BackgroundCompiler({"ok": lambda: 1, "bad": refused})
        with pytest.raises(RuntimeError, match="Mosaic said no"):
            bg.join()

    def test_configure_never_replaces_a_dir_jax_holds(self, tmp_cache):
        # the set_cache_dir test hook (like JAX_COMPILATION_CACHE_DIR)
        # put a directory in jax.config: configure() must keep it
        assert cc.configure() is True
        assert jax.config.jax_compilation_cache_dir == tmp_cache


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PLACEMENT_PROBE = """
import json, sys
sys.path.insert(0, %r)
import jax
writes = []
_update = jax.config.update
def update(name, value):
    writes.append([name, value])
    return _update(name, value)
jax.config.update = update
from dmlc_core_tpu.base import compile_cache as cc
cc.configure()
print(json.dumps({"dir": cc.cache_dir(), "writes": writes}))
""" % _REPO


def _placement(env_dir):
    """Run configure() in a fresh process; returns the directory in
    effect and every (name, value) it wrote to jax.config."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PLACEMENT_PROBE],
                         env=env, capture_output=True, text=True,
                         timeout=120, check=True).stdout
    return json.loads(out.strip().splitlines()[-1])


class TestCachePlacement:
    def test_env_dir_is_the_only_dir(self, tmp_path):
        want = str(tmp_path / "from_outside")
        got = _placement(want)
        assert got["dir"] == want
        # nothing wrote a directory into jax.config: jax adopted the
        # environment's, the library only opened the size/time floors
        assert [w for w in got["writes"]
                if w[0] == "jax_compilation_cache_dir"] == []

    def test_unset_is_one_fixed_dir_in_the_checkout(self):
        a, b = _placement(None), _placement(None)
        want = os.path.join(_REPO, ".compile_cache")
        assert a["dir"] == b["dir"] == want
        with open(os.path.join(_REPO, ".gitignore")) as f:
            assert ".compile_cache/" in f.read().split()

    def test_old_knob_is_read_nowhere(self):
        from dmlc_core_tpu.base import knobs

        needle = "DMLC_COMPILE_" + "CACHE_DIR"
        assert needle not in knobs.names()
        hits = []
        for top in ("dmlc_core_tpu", "scripts", "tests", "doc", "examples",
                    "bench.py", "chip_smoke.py", "README.md"):
            path = os.path.join(_REPO, top)
            files = ([path] if os.path.isfile(path) else
                     [os.path.join(d, f) for d, _, fs in os.walk(path)
                      for f in fs if f.endswith((".py", ".md", ".sh"))])
            for fp in files:
                with open(fp, errors="replace") as f:
                    if needle in f.read():
                        hits.append(os.path.relpath(fp, _REPO))
        assert hits == []


class TestOverlapParity:
    def test_overlap_bit_identical_to_inline(self, monkeypatch):
        m1, X, y = _tiny_fit(n_trees=3, seed=1)          # overlap (default)
        assert m1.last_compile_seconds is not None or \
            m1.last_compile_cache is None   # handle consumed or cache-warm
        # the serial path a multi-worker job takes: no warmup handle
        monkeypatch.setattr(HistGBT, "_maybe_start_warmup",
                            lambda self, *a, **k: None)
        m2 = HistGBT(n_trees=3, max_depth=2, n_bins=8)
        m2.fit(X, y, warmup_rounds=1)
        assert m2.last_compile_seconds is None           # inline path
        _assert_same_trees(_trees(m1), _trees(m2))
        np.testing.assert_array_equal(m1.predict(X), m2.predict(X))

    def test_overlap_with_sampling_and_eval_set(self, monkeypatch):
        rng = np.random.default_rng(2)
        X = rng.normal(size=(300, 5)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        Xv, yv = X[:80], y[:80]
        kw = dict(n_trees=4, max_depth=3, n_bins=16, subsample=0.7,
                  colsample_bytree=0.8, seed=7)
        m1 = HistGBT(**kw)
        m1.fit(X, y, warmup_rounds=1, eval_set=(Xv, yv))
        monkeypatch.setattr(HistGBT, "_maybe_start_warmup",
                            lambda self, *a, **k: None)
        m2 = HistGBT(**kw)
        m2.fit(X, y, warmup_rounds=1, eval_set=(Xv, yv))
        _assert_same_trees(_trees(m1), _trees(m2))
        assert m1.eval_history == m2.eval_history

    def test_warmup_handle_ignored_on_param_drift(self):
        # a handle warmed for one config must not serve another: mutate
        # n_trees between make_device_data (kickoff) and the fit
        rng = np.random.default_rng(3)
        X = rng.normal(size=(200, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        m = HistGBT(n_trees=2, max_depth=2, n_bins=8)
        dd = m.make_device_data(X, y)
        assert m._pending_warmup is not None
        m.param.n_trees = 3                  # drift: K/rem change
        m.fit_device(dd, warmup_rounds=1)
        assert len(m.trees) == 3             # inline fallback, correct
        assert m.last_compile_seconds is None

    def test_schedule_helper(self):
        assert _rounds_schedule(100) == (25, 0)
        assert _rounds_schedule(30) == (25, 5)
        assert _rounds_schedule(100, eval_every=7) == (7, 2)
        assert _rounds_schedule(3) == (3, 0)


class TestStreamedIngest:
    def test_chunked_bins_bit_identical(self, monkeypatch):
        rng = np.random.default_rng(4)
        X = rng.normal(size=(500, 6)).astype(np.float32)
        y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
        Xv = rng.normal(size=(200, 6)).astype(np.float32)
        yv = (Xv[:, 0] + Xv[:, 1] > 0).astype(np.float32)
        m1 = HistGBT(n_trees=3, max_depth=3, n_bins=16)
        m1.fit(X, y, eval_set=(Xv, yv))
        # tiny chunks force the streamed path for train AND eval ingest
        monkeypatch.setenv("DMLC_INGEST_CHUNK_ROWS", "96")
        m2 = HistGBT(n_trees=3, max_depth=3, n_bins=16)
        m2.fit(X, y, eval_set=(Xv, yv))
        _assert_same_trees(_trees(m1), _trees(m2))
        assert m1.eval_history == m2.eval_history
        np.testing.assert_array_equal(m1.predict(Xv), m2.predict(Xv))

    def test_chunked_missing_mode(self, monkeypatch):
        rng = np.random.default_rng(5)
        X = rng.normal(size=(400, 5)).astype(np.float32)
        X[rng.random(X.shape) < 0.1] = np.nan
        y = (np.nan_to_num(X[:, 0]) > 0).astype(np.float32)
        m1 = HistGBT(n_trees=3, max_depth=2, n_bins=16)
        m1.fit(X, y)
        assert m1._missing
        monkeypatch.setenv("DMLC_INGEST_CHUNK_ROWS", "64")
        m2 = HistGBT(n_trees=3, max_depth=2, n_bins=16)
        m2.fit(X, y)
        _assert_same_trees(_trees(m1), _trees(m2))

    def test_streaming_disabled_by_zero(self, monkeypatch):
        monkeypatch.setenv("DMLC_INGEST_CHUNK_ROWS", "0")
        m, X, _ = _tiny_fit(seed=6)
        assert len(m.trees) == 2             # whole-matrix path still fine


class TestColdStartEvidence:
    def test_breakdown_fields_populated(self):
        m, X, y = _tiny_fit(n_trees=3, rows=256, seed=8)
        assert m.last_bin_seconds is not None and m.last_bin_seconds >= 0
        assert m.last_warm_dispatch_seconds is not None
        assert m.last_warmup_seconds >= m.last_warm_dispatch_seconds
        # fit_device on a fresh handle reuses the process-wide AOT
        # executables: zero compile on the critical path
        dd = m.make_device_data(X, y)
        m2 = HistGBT(n_trees=3, max_depth=2, n_bins=8)
        m2.fit_device(dd, warmup_rounds=1)
        assert len(m2.trees) == 3


class TestServePrewarm:
    def test_env_gated_prewarm_and_gauge(self, monkeypatch):
        from dmlc_core_tpu.serve import ModelRunner
        from dmlc_core_tpu.serve.instruments import serve_metrics

        m, X, _ = _tiny_fit(seed=9)
        monkeypatch.setenv("DMLC_SERVE_PREWARM", "1")
        r = ModelRunner(m, max_batch=32, min_bucket=8, name="prewarm-t")
        assert r.compiled_shapes == {8, 16, 32}
        if base_metrics.enabled():
            g = serve_metrics()["compiled_shapes"]
            assert g.value(runner="prewarm-t") == r.shape_bound
        # pre-warmed runner scores identically to the bare model
        np.testing.assert_array_equal(r.predict(X[:5]), m.predict(X[:5]))

    def test_warmup_needs_feature_width(self):
        from dmlc_core_tpu.serve import ModelRunner
        from dmlc_core_tpu.base.logging import Error

        class Opaque:
            def predict(self, X):
                return np.zeros(len(X), np.float32)

        r = ModelRunner(Opaque(), max_batch=16, min_bucket=8)
        with pytest.raises(Error):
            r.warmup()
        assert r.warmup(n_features=3) >= 0.0
        assert r.compiled_shapes == {8, 16}
