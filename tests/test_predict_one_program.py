"""A scoring slab is ONE compiled program (`_predict_slab`: bin, every
tree chunk, the objective's transform): its answers are the bytes of the
five or six programs it replaced, the record of the spans counts one
enqueue a slab, it compiles when a forest crosses a chunk mark and at no
other time, and its device scopes and memory are the ones the benchmark
reads.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu.base.logging import Error
from dmlc_core_tpu.data.iter import RowBlockIter
from dmlc_core_tpu.models import histgbt as G
from dmlc_core_tpu.models.gbt_objectives import _Logistic
from dmlc_core_tpu.models.histgbt import HistGBT
from dmlc_core_tpu.parallel.mesh import local_mesh
from dmlc_core_tpu.utils.profiler import (global_tracer, op_log, set_tracing,
                                          tracing_enabled)

CHUNK = G._TREE_CHUNK
_KINDS = {
    "binary": {"base_score": 0.25},
    "regression": {"objective": "reg:squarederror", "base_score": -1.5},
    "multiclass": {"objective": "multi:softmax", "num_class": 3},
    "missing": {},
}
#: not a multiple of the descent's row tile
_ROWS = 301
assert _ROWS % G._ROW_TILE


@pytest.fixture(scope="module")
def fitted():
    """kind -> (a model of 3 fitted trees, X): the cuts, the mode and
    the tables every forest of this file is made from."""
    rng = np.random.default_rng(0)
    out = {}
    for kind, params in _KINDS.items():
        X = rng.normal(size=(1500, 6)).astype(np.float32)
        score = X[:, 0] + 0.5 * X[:, 1] ** 2
        y = {"multiclass": np.digitize(score, [0.0, 1.0]),
             "regression": score}.get(kind, score > 0.5).astype(np.float32)
        if kind == "missing":
            X[rng.random(X.shape) < 0.1] = np.nan
        model = HistGBT(n_trees=3, max_depth=3, n_bins=16,
                        mesh=local_mesh(1), **params)
        out[kind] = (model.fit(X, y), X[:_ROWS])
    assert "dir" in out["missing"][0].trees[0]
    return out


def _forest(fitted, kind, n_trees):
    """A model of ``n_trees`` trees: the fitted three over and over, each
    scaled a little so that no two trees add the same leaves."""
    src, X = fitted[kind]
    model = HistGBT(mesh=src.mesh, **src.param.to_dict())
    model.cuts, model._missing = src.cuts, src._missing
    model.trees = [
        {k: (v * np.float32(1 + i / 257) if k == "leaf" else np.array(v))
         for k, v in src.trees[i % 3].items()} for i in range(n_trees)]
    return model, X


def _as_five_programs(model, X, output_margin, n_trees=None):
    """`predict` of one slab as it was: bin, then `_apply_trees` over the
    same chunks from ``jnp.full(base_score)``, then the transform, each
    a program of its own."""
    stacked = model._stacked_trees(model._resolve_trees(n_trees))
    bins = model._bin_matrix(jnp.asarray(X))
    margin = model._apply_trees(
        bins, stacked, jnp.full(model._margin_shape(len(X)),
                                model.param.base_score, jnp.float32))
    out = np.asarray(margin if output_margin
                     else model._obj.transform(margin))
    # several classes: class-major on the device, [n, K] for the caller
    return out if out.ndim == 1 else np.ascontiguousarray(out.T)


# -- (a) the answers ---------------------------------------------------------
@pytest.mark.parametrize("n_trees", [1, 64, 65, 100, 130])
@pytest.mark.parametrize("output_margin", [True, False],
                         ids=["margin", "transformed"])
@pytest.mark.parametrize("kind", list(_KINDS))
def test_bytes_are_those_of_the_programs_it_replaced(fitted, kind,
                                                     output_margin, n_trees):
    model, X = _forest(fitted, kind, n_trees)
    got = model.predict(X, output_margin=output_margin)
    want = _as_five_programs(model, X, output_margin)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    assert np.isfinite(got).all() and len(np.unique(got)) > 1


def test_a_prefix_is_the_prefix(fitted):
    model, X = _forest(fitted, "binary", 100)
    assert model.predict(X, n_trees=70).tobytes() \
        == _as_five_programs(model, X, False, n_trees=70).tobytes()


# -- (b) one enqueue a slab --------------------------------------------------
def _predict_records(before):
    return [r for r in op_log()[before:] if r["name"] == "dmlc.predict"]


@pytest.mark.parametrize("kind", list(_KINDS))
@pytest.mark.parametrize("batch,slabs", [(None, 1), (200, 2)])
def test_the_record_counts_one_program_a_slab(fitted, monkeypatch, kind,
                                              batch, slabs):
    model, X = _forest(fitted, kind, 100)
    if batch:
        monkeypatch.setattr(HistGBT, "_PREDICT_BATCH", batch)
    before = len(op_log())
    got = model.predict(X)
    (rec,) = _predict_records(before)
    assert rec["counts"]["programs"] == slabs
    assert rec["children"]["dmlc.predict.dispatch"][0] == slabs
    assert rec["children"]["dmlc.predict.put"][0] == slabs
    if batch:      # the slabs' answers are the one slab's
        monkeypatch.undo()
        assert got.tobytes() == model.predict(X).tobytes()


def test_predict_iter_counts_its_pages(fitted, tmp_path):
    model, X = _forest(fitted, "binary", 70)
    data = os.path.join(str(tmp_path), "p.libsvm")
    with open(data, "w") as f:
        for row in X:
            f.write("0 " + " ".join(f"{j}:{v:.6f}"
                                    for j, v in enumerate(row)) + "\n")
    it = RowBlockIter.create(data, 0, 1, "libsvm")
    before = len(op_log())
    try:
        out = model.predict_iter(it, batch_rows=128)      # 128 + 128 + 45
    finally:
        it.close()
    (rec,) = _predict_records(before)
    assert len(out) == _ROWS
    assert rec["counts"]["programs"] == 3
    assert rec["children"]["dmlc.predict.dispatch"][0] == 3


def test_no_rows_no_program(fitted):
    model, X = _forest(fitted, "binary", 3)
    before = len(op_log())
    assert model.predict(X[:0]).shape == (0,)
    (rec,) = _predict_records(before)
    assert rec["counts"]["programs"] == 0
    assert "dmlc.predict.dispatch" not in rec["children"]


def test_nan_is_refused_before_anything_is_put(fitted):
    model, X = _forest(fitted, "binary", 100)
    X = X.copy()
    X[7, 2] = np.nan
    before = len(op_log())
    with pytest.raises(Error, match="contains NaN"):
        model.predict(X)
    (rec,) = _predict_records(before)
    assert rec["counts"]["programs"] == 0
    assert "dmlc.predict.put" not in rec["children"]
    # a model that learned a direction for NaN takes the same rows
    missing, _ = _forest(fitted, "missing", 100)
    assert np.isfinite(missing.predict(X)).all()


def test_the_dispatch_span_says_how_many_programs(fitted):
    model, X = _forest(fitted, "binary", 100)
    tr, was = global_tracer(), tracing_enabled()
    set_tracing(True)
    tr.clear()
    try:
        model.predict(X)
        events = {e["name"]: e["args"] for e in tr.events()}
    finally:
        set_tracing(was)
        tr.clear()
    assert events["dmlc.predict.dispatch"]["programs"] == 1
    assert events["dmlc.predict"]["programs"] == 1


# -- (c) what compiles, and when ---------------------------------------------
def _grow(model, fitted, kind, n_trees):
    more, _ = _forest(fitted, kind, n_trees)
    model.trees = model.trees + more.trees[len(model.trees):]


def test_a_program_per_chunk_count_and_no_other(fitted):
    model, X = _forest(fitted, "binary", 100)
    X = X[:257]                 # a slab shape no other test compiles for
    model.predict(X)
    before = G._predict_slab._cache_size()
    model.predict(X)
    model.predict(X, n_trees=70)                  # two chunks, as 100
    _grow(model, fitted, "binary", 120)
    model.predict(X)
    assert G._predict_slab._cache_size() == before
    _grow(model, fitted, "binary", 128)
    model.predict(X)
    assert G._predict_slab._cache_size() == before
    _grow(model, fitted, "binary", 129)           # crosses a chunk mark
    model.predict(X)
    model.predict(X)
    assert G._predict_slab._cache_size() == before + 1


# -- (d) the scopes and the memory the benchmark reads -----------------------
S = jax.ShapeDtypeStruct
_DEPTH = 6
_TABLE = S((CHUNK, _DEPTH, 1 << (_DEPTH - 1)), jnp.int32)
_LEAVES = S((CHUNK, 1 << _DEPTH), jnp.float32)


def _lowered(n, F, chunks, dirs=False, transform=None):
    chunk = {"feat": _TABLE, "thr": _TABLE, "leaf": _LEAVES}
    if dirs:
        chunk["dir"] = _TABLE
    return G._predict_slab.lower(
        S((n, F), jnp.float32), S((F, 255), jnp.float32),
        [chunk] * chunks, _DEPTH, 255 if dirs else -1, 0.0, transform)


@pytest.mark.parametrize("dirs", [False, True], ids=["plain", "missing"])
def test_the_program_carries_both_scopes_and_no_gather(dirs):
    lowered = _lowered(16_384, 28, 2, dirs, _Logistic.transform)
    text = lowered.compile().as_text()
    op_names = [line.split('op_name="', 1)[1].split('"', 1)[0]
                for line in text.splitlines() if 'op_name="' in line]
    for scope in ("dmlc.bin", "dmlc.descend"):
        assert any(scope in name.split("/") for name in op_names), scope
    assert "gather" not in lowered.as_text() and "gather" not in text


def test_compile_time_does_not_grow_with_the_chunks():
    """Sixteen chunks (1,000 trees) are one descent, as two are: the
    chunks are scanned, not inlined."""
    def descents(chunks):
        return _lowered(1024, 28, chunks).as_text().count("stablehlo.while")
    assert descents(16) == descents(2)


@pytest.mark.parametrize("n,F", [(HistGBT._PREDICT_BATCH, 28),
                                 (100_000, 2000)])
def test_memory_is_that_of_its_parts(n, F):
    """The float32 slab is an argument, alive while the descent runs:
    beside it the program holds the slab's bins and the blocks of ONE
    `_predict_trees` — bounded in n by `_PREDICT_BATCH`, as the programs
    it replaced were (tests/test_descend.py)."""
    descent = G._predict_trees.lower(
        S((n, F), jnp.uint8), _TABLE, _TABLE, _LEAVES, _DEPTH, 0.0,
        S((n,), jnp.float32), None, -1).compile().memory_analysis()
    mem = _lowered(n, F, 2).compile().memory_analysis()
    assert mem.argument_size_in_bytes >= n * F * 4
    assert mem.temp_size_in_bytes \
        <= n * F + descent.temp_size_in_bytes + (64 << 20)
