"""The device forest stays on the model, chunk by chunk
(`HistGBT._stacked_trees`): a chunk is built when its trees are new and
comes back when the same host arrays are asked for again, whoever
changed `model.trees` and however; the answers are those of a model
that never kept anything; the span and the counter say what a call did.
"""
import contextlib
import copy
import io
import os
import pickle
import threading
import types

import numpy as np
import pytest
from jax.sharding import Mesh

from dmlc_core_tpu.data.iter import RowBlockIter
from dmlc_core_tpu.models import histgbt as G
from dmlc_core_tpu.models.gbt_split import gbt_metrics
from dmlc_core_tpu.models.histgbt import HistGBT
from dmlc_core_tpu.parallel.mesh import local_mesh
from dmlc_core_tpu.utils.profiler import (global_tracer, set_tracing,
                                          tracing_enabled)

CHUNK = G._TREE_CHUNK
_KINDS = {
    "binary": {},
    "multiclass": {"objective": "multi:softmax", "num_class": 3},
    "missing": {},
}


def _data(kind, n=1500, F=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    score = X[:, 0] + 0.5 * X[:, 1] ** 2
    y = ((score > 0.5).astype(np.float32) if kind != "multiclass"
         else np.digitize(score, [0.0, 1.0]).astype(np.float32))
    if kind == "missing":
        X[rng.random(X.shape) < 0.1] = np.nan
    return X, y


@pytest.fixture(scope="module")
def fitted():
    """kind -> (a model of 3 fitted trees, its X): the cuts, the mode
    and the tables every other forest of this file is made from."""
    out = {}
    for kind, params in _KINDS.items():
        X, y = _data(kind)
        model = HistGBT(n_trees=3, max_depth=3, n_bins=16,
                        mesh=local_mesh(1), **params)
        out[kind] = (model.fit(X, y), X[:300])
    return out


def _fresh(tree):
    """The same tree in new arrays."""
    return {k: np.array(v) for k, v in tree.items()}


def _forest(fitted, kind, n_trees):
    """A model of ``n_trees`` trees (the fitted three, over and over,
    each in arrays of its own) that has stacked nothing yet, and X."""
    src, X = fitted[kind]
    model = HistGBT(mesh=src.mesh, **src.param.to_dict())
    model.cuts, model._missing = src.cuts, src._missing
    model.trees = [_fresh(src.trees[i % 3]) for i in range(n_trees)]
    return model, X


def _cold(model):
    """A model of equal trees, none of them ``model``'s arrays, that has
    never stacked."""
    cold = HistGBT(mesh=model.mesh, **model.param.to_dict())
    cold.cuts, cold._missing = model.cuts, model._missing
    cold.trees = [_fresh(t) for t in model.trees]
    cold.best_iteration = model.best_iteration
    cold._early_stopped = getattr(model, "_early_stopped", False)
    return cold


def _stack_as_before(trees):
    """`_stacked_trees` as it was when it kept nothing, on the host."""
    keys = ("feat", "thr", "leaf") + (("dir",) if "dir" in trees[0] else ())
    chunks = []
    for lo in range(0, len(trees), CHUNK):
        part = trees[lo:lo + CHUNK]
        stacked = {k: np.stack([t[k] for t in part]) for k in keys}
        pad = CHUNK - len(part)
        chunks.append({
            k: np.concatenate([v, np.zeros((pad,) + v.shape[1:], v.dtype)])
            for k, v in stacked.items()})
    return chunks


def _assert_stack_is_cold(model, trees):
    got = model._stacked_trees(trees)
    want = _stack_as_before(trees)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert list(g) == list(w)
        for k in w:
            assert g[k].dtype == w[k].dtype
            np.testing.assert_array_equal(np.asarray(g[k]), w[k])


@contextlib.contextmanager
def _watch(engine="incore"):
    """What the calls inside did, by both accounts: ``.spans`` (the
    counts of each ``dmlc.predict.stack``), ``.hit`` and ``.built`` (the
    counter's growth); and the two accounts agree."""
    count = gbt_metrics()["forest_chunks"]

    def read():
        return {r: count.value(engine=engine, result=r)
                for r in ("hit", "built")}

    before, tr, was = read(), global_tracer(), tracing_enabled()
    seen = types.SimpleNamespace()
    set_tracing(True)
    tr.clear()
    try:
        yield seen
    finally:
        seen.spans = [e["args"] for e in tr.events()
                      if e["name"] == "dmlc.predict.stack"]
        set_tracing(was)
        tr.clear()
    after = read()
    seen.hit = after["hit"] - before["hit"]
    seen.built = after["built"] - before["built"]
    assert seen.hit == sum(s["chunks_hit"] for s in seen.spans)
    assert seen.built == sum(s["chunks_built"] for s in seen.spans)
    assert all((s["bytes"] == 0) == (s["chunks_built"] == 0)
               for s in seen.spans)


def _chunks(n_trees):
    return -(-n_trees // CHUNK)


# -- the same forest again ----------------------------------------------
@pytest.mark.parametrize("n_trees", [3, 64, 100])
@pytest.mark.parametrize("kind", list(_KINDS))
def test_second_predict_builds_nothing(fitted, kind, n_trees):
    model, X = _forest(fitted, kind, n_trees)
    with _watch() as first:
        a = model.predict(X)
    with _watch() as second:
        b = model.predict(X)
        c = model.predict(X, output_margin=True)
    n = _chunks(n_trees)
    assert (first.hit, first.built) == (0, n)
    assert first.spans[0]["trees"] == n_trees
    assert first.spans[0]["bytes"] == sum(
        v.nbytes for ch in _stack_as_before(model.trees)
        for v in ch.values())
    # a span on every call, hit or not
    assert len(second.spans) == 2
    assert (second.hit, second.built) == (2 * n, 0)
    cold = _cold(model)
    assert a.tobytes() == b.tobytes() == cold.predict(X).tobytes()
    assert c.tobytes() == cold.predict(X, output_margin=True).tobytes()
    _assert_stack_is_cold(model, model.trees)


@pytest.mark.parametrize("kind", list(_KINDS))
def test_predict_proba_and_leaf(fitted, kind):
    """`predict_proba` goes through `predict`; `predict_leaf` stacks for
    itself and neither reads nor fills what the model keeps."""
    model, X = _forest(fitted, kind, 70)
    with _watch() as w:
        leaf = model.predict_leaf(X)
    assert w.spans == [] and model._forest_chunks == ()
    with _watch() as w:
        p1 = model.predict_proba(X)
        p2 = model.predict_proba(X)
    assert (w.hit, w.built) == (2, 2)
    assert p1.tobytes() == p2.tobytes() \
        == _cold(model).predict_proba(X).tobytes()
    assert leaf.tobytes() == _cold(model).predict_leaf(X).tobytes()


# -- a forest that grows -------------------------------------------------
@pytest.mark.parametrize("kind", ["binary", "multiclass"])
def test_continued_fit_rebuilds_the_partial_chunk_only(kind):
    """65 trees, then continued `fit` to 70 and to 130: the full first
    chunk is built once; each `fit`'s margin replay finds the forest the
    `predict` before it left."""
    X, y = _data(kind, n=600)
    model = HistGBT(n_trees=65, max_depth=2, n_bins=8, mesh=local_mesh(1),
                    **_KINDS[kind])
    model.fit(X, y)
    with _watch() as w:
        model.predict(X)
    assert (w.hit, w.built) == (0, 2)
    first = model._forest_chunks[0]
    for add, total, built in ((5, 70, 1), (60, 130, 2)):
        model.param.n_trees = add
        with _watch() as w:
            model.fit(X, y)            # the replay: the forest as it was
        assert len(model.trees) == total
        assert w.built == 0 and w.hit == len(w.spans) * _chunks(total - add)
        with _watch() as w:
            got = model.predict(X)
        assert (w.hit, w.built) == (_chunks(total) - built, built)
        assert model._forest_chunks[0] is first
        assert got.tobytes() == _cold(model).predict(X).tobytes()
        _assert_stack_is_cold(model, model.trees)


def test_eval_set_replay_of_a_continued_fit():
    """A continued `fit` with an `eval_set` replays the forest twice
    (train rows, validation rows): the second replay is all hits."""
    X, y = _data("binary", n=600)
    model = HistGBT(n_trees=4, max_depth=2, n_bins=8, mesh=local_mesh(1))
    model.fit(X, y)
    with _watch() as w:
        model.fit(X, y, eval_set=(X[:200], y[:200]))
    assert [(s["chunks_hit"], s["chunks_built"]) for s in w.spans] \
        == [(0, 1), (1, 0)]
    assert len(model.trees) == 8


def test_appended_trees(fitted):
    """`model.trees.append` from outside (the external engine's loop,
    the stream refresh): the list is the same object and as long as a
    full chunk allows, the full chunks stay."""
    model, X = _forest(fitted, "binary", 2 * CHUNK)
    model.predict(X)
    kept = model._forest_chunks
    model.trees.append(_fresh(model.trees[0]))
    with _watch() as w:
        got = model.predict(X)
    assert (w.hit, w.built) == (2, 1)
    assert model._forest_chunks[:2] == kept
    assert got.tobytes() == _cold(model).predict(X).tobytes()


# -- prefixes ------------------------------------------------------------
@pytest.mark.parametrize("n_trees", [1, 63, 64, 65, 128, 129])
def test_prefix_shares_the_full_chunks(fitted, n_trees):
    model, X = _forest(fitted, "binary", 130)
    whole = model.predict(X)
    full = n_trees // CHUNK            # chunks the prefix has whole
    with _watch() as w:
        got = model.predict(X, n_trees=n_trees)
    assert (w.hit, w.built) == (full, _chunks(n_trees) - full)
    cold = _cold(model)
    assert got.tobytes() == cold.predict(X, n_trees=n_trees).tobytes()
    _assert_stack_is_cold(model, model.trees[:n_trees])
    # the whole forest's partial chunk was kept aside: going back builds
    # only the full chunks the prefix did not ask for
    with _watch() as w:
        again = model.predict(X)
    assert w.built == 2 - full
    assert again.tobytes() == whole.tobytes()


def test_prefix_and_whole_forest_take_turns(fitted):
    """An early-stop winner of 70 against a forest of 100: both partial
    chunks stay, so neither call builds after its first."""
    model, X = _forest(fitted, "binary", 100)
    model.predict(X)
    model.predict(X, n_trees=70)
    with _watch() as w:
        for _ in range(3):
            a = model.predict(X, n_trees=70)
            b = model.predict(X)
    assert (w.hit, w.built) == (12, 0)
    cold = _cold(model)
    assert a.tobytes() == cold.predict(X, n_trees=70).tobytes()
    assert b.tobytes() == cold.predict(X).tobytes()


def test_early_stopped_model():
    X, y = _data("binary", n=800)
    model = HistGBT(n_trees=40, max_depth=2, n_bins=8, mesh=local_mesh(1),
                    learning_rate=1.0)
    rng = np.random.default_rng(5)
    model.fit(X, y, eval_set=(X[:300], rng.permutation(y[:300])),
              eval_every=2, early_stopping_rounds=2)
    assert model._early_stopped and model.best_iteration is not None
    n_best = model.best_iteration + 1
    assert n_best < len(model.trees)
    with _watch() as w:
        a = model.predict(X)
        b = model.predict(X)
        whole = model.predict(X, n_trees=len(model.trees))
    assert w.spans[0]["trees"] == w.spans[1]["trees"] == n_best
    assert [s["chunks_built"] for s in w.spans] == [1, 0, 1]
    cold = _cold(model)
    assert a.tobytes() == b.tobytes() == cold.predict(X).tobytes()
    assert whole.tobytes() == cold.predict(
        X, n_trees=len(model.trees)).tobytes()


def test_keeps_the_last_forest_and_one_partial_chunk(fitted):
    model, X = _forest(fitted, "binary", 3 * CHUNK + 10)
    for n_trees, kept in ((None, 4), (CHUNK + 5, 3), (5, 2), (None, 5),
                          (2 * CHUNK, 3), (CHUNK, 2)):
        model.predict(X, n_trees=n_trees)
        asked = _chunks(n_trees or len(model.trees))
        assert len(model._forest_chunks) == kept <= asked + 1
        assert [c.index for c in model._forest_chunks[:asked]] \
            == list(range(asked))
        assert all(c.n_trees < CHUNK for c in model._forest_chunks[asked:])


# -- trees changed from outside the class -------------------------------
def _cut(model, tmp_path):
    model.trees = model.trees[:70]         # parallel/recovery.py's cut
    return 1, 1                            # chunk 0 whole; 6 of 36 left


def _replaced_list(model, tmp_path):
    model.trees = list(model.trees)        # a new list of the same trees
    return 2, 0


def _reloaded(model, tmp_path):
    path = os.path.join(str(tmp_path), "m.bin")
    model.save_model(path)
    model.trees = HistGBT.load_model(path, mesh=model.mesh).trees
    return 0, 2                            # recovery.py: m.trees = loaded.trees


def _rebuilt_dict(model, tmp_path):
    model.trees[CHUNK + 1] = _fresh(model.trees[CHUNK + 1])
    return 1, 1                            # equal values, new arrays


def _one_table_replaced(model, tmp_path):
    model.trees[2]["leaf"] = model.trees[2]["leaf"] * np.float32(0.5)
    return 1, 1                            # the dict is the one it was


def _reset(model, tmp_path):
    model.trees = [_fresh(t) for t in model.trees[:3]]   # fit_device's reset
    return 0, 1


def _swapped(model, tmp_path):
    model.trees[0], model.trees[1] = model.trees[1], model.trees[0]
    return 1, 1                            # the same arrays, another order


_CHANGES = [_cut, _replaced_list, _reloaded, _rebuilt_dict,
            _one_table_replaced, _reset, _swapped]


@pytest.mark.parametrize("change", _CHANGES,
                         ids=[c.__name__.strip("_") for c in _CHANGES])
@pytest.mark.parametrize("kind", ["binary", "missing"])
def test_changed_trees_are_a_miss(fitted, kind, change, tmp_path):
    model, X = _forest(fitted, kind, 100)
    model.predict(X)
    hit, built = change(model, tmp_path)
    with _watch() as w:
        got = model.predict(X)
    assert (w.hit, w.built) == (hit, built)
    assert got.tobytes() == _cold(model).predict(X).tobytes()
    _assert_stack_is_cold(model, model.trees)
    with _watch() as w:
        model.predict(X)
    assert w.built == 0


def test_load_model_starts_cold_and_then_hits(fitted, tmp_path):
    model, X = _forest(fitted, "multiclass", 70)
    want = model.predict(X)
    path = os.path.join(str(tmp_path), "m.bin")
    model.save_model(path)
    loaded = HistGBT.load_model(path, mesh=model.mesh)
    assert loaded._forest_chunks == ()
    with _watch() as w:
        a, b = loaded.predict(X), loaded.predict(X)
    assert (w.hit, w.built) == (2, 2)
    assert a.tobytes() == b.tobytes() == want.tobytes()


def test_a_freed_array_s_identity_cannot_hit(fitted):
    """The chunk holds the arrays it was built from, so a table made
    after the old one was dropped never has its identity."""
    model, X = _forest(fitted, "binary", 5)
    model.predict(X)
    held = [id(a) for a in model._forest_chunks[0].hosts]
    for _ in range(50):
        model.trees[0] = _fresh(model.trees[0])    # the old dict is garbage
        assert not set(map(id, model.trees[0].values())) & set(held)
    with _watch() as w:
        model.predict(X)
    assert (w.hit, w.built) == (0, 1)


# -- the other callers ---------------------------------------------------
def test_stacked_trees_of_any_list(fitted):
    """tests/test_descend.py's use: a list that is not `model.trees`."""
    model, _ = _forest(fitted, "missing", 3)
    other = [_fresh(model.trees[i % 3]) for i in range(70)]
    with _watch() as w:
        _assert_stack_is_cold(model, other)
        _assert_stack_is_cold(model, other)
        _assert_stack_is_cold(model, model.trees)
    assert [(s["chunks_hit"], s["chunks_built"]) for s in w.spans] \
        == [(0, 2), (2, 0), (0, 1)]


def _write_libsvm(path, X, y=None):
    with open(path, "w") as f:
        for i, row in enumerate(X):
            f.write(f"{0 if y is None else int(y[i])} " + " ".join(
                f"{j}:{v:.6f}" for j, v in enumerate(row)) + "\n")


def test_predict_iter_stacks_once_a_call(fitted, tmp_path):
    model, X = _forest(fitted, "binary", 70)
    data = os.path.join(str(tmp_path), "p.libsvm")
    _write_libsvm(data, X)
    outs = []
    with _watch() as w:
        for _ in range(2):
            it = RowBlockIter.create(data, 0, 1, "libsvm")
            outs.append(model.predict_iter(it, batch_rows=64))   # 5 slabs
            it.close()
    assert [(s["chunks_hit"], s["chunks_built"]) for s in w.spans] \
        == [(0, 2), (2, 0)]
    assert outs[0].tobytes() == outs[1].tobytes()
    it = RowBlockIter.create(data, 0, 1, "libsvm")
    assert _cold(model).predict_iter(it, batch_rows=64).tobytes() \
        == outs[0].tobytes()
    it.close()


@pytest.mark.parametrize("budget", [None, "40000"],
                         ids=["cached", "streamed"])
def test_external_engine_replay(budget, tmp_path, monkeypatch):
    """`fit_external` on a model that has trees replays them under the
    counter's `external` label: built on the first replay, kept for the
    `predict` after it (the four new trees make the chunk anew)."""
    if budget:
        monkeypatch.setenv("DMLC_TPU_EXTERNAL_DEVICE_BUDGET", budget)
    X, _ = _data("binary", n=1500, F=4)
    data = os.path.join(str(tmp_path), "t.libsvm")
    _write_libsvm(data, X, X[:, 0] > 0)
    model = HistGBT(n_trees=4, max_depth=2, n_bins=8, hist_method="segment",
                    mesh=local_mesh(1))

    def fit():
        it = RowBlockIter.create(data, 0, 1, "libsvm")
        model.fit_external(it)
        it.close()

    fit()
    incore = gbt_metrics()["forest_chunks"].value(engine="incore",
                                                  result="built")
    with _watch("external") as ext:
        fit()
    assert len(model.trees) == 8
    assert (ext.hit, ext.built) == (0, 1)
    assert gbt_metrics()["forest_chunks"].value(
        engine="incore", result="built") == incore
    with _watch() as w:
        got = model.predict(X)
    assert (w.hit, w.built) == (0, 1)
    with _watch("external") as ext:
        fit()
    assert (ext.hit, ext.built) == (1, 0)
    assert got.tobytes() == _cold(model).predict(X, n_trees=8).tobytes()


# -- what leaves the process ---------------------------------------------
class _MeshById(pickle.Pickler):
    """A `Mesh` holds devices, which do not pickle: name it instead."""

    def persistent_id(self, obj):
        return "mesh" if isinstance(obj, Mesh) else None


def _pickled(model):
    buf = io.BytesIO()
    _MeshById(buf).dump(model)

    class Load(pickle.Unpickler):
        def persistent_load(self, pid):
            return model.mesh

    return Load(io.BytesIO(buf.getvalue())).load()


def _saved(model, tmp_path):
    path = os.path.join(str(tmp_path), "m.bin")
    model.save_model(path)
    with open(path, "rb") as f:
        assert b"_forest" not in f.read()
    return HistGBT.load_model(path, mesh=model.mesh)


_TRIPS = {
    "save_model": _saved,
    "pickle": lambda m, tmp: _pickled(m),
    "deepcopy": lambda m, tmp: copy.deepcopy(m, {id(m.mesh): m.mesh}),
    "copy": lambda m, tmp: copy.copy(m),
}


@pytest.mark.parametrize("trip", list(_TRIPS))
def test_round_trip_carries_no_device_forest(fitted, trip, tmp_path):
    model, X = _forest(fitted, "missing", 70)
    want = model.predict(X)
    assert len(model._forest_chunks) == 2
    assert model.__getstate__()["_forest_chunks"] == ()
    other = _TRIPS[trip](model, tmp_path)
    assert other._forest_chunks == ()
    assert not any(isinstance(v, G._ForestChunk) for v in vars(other).values())
    with _watch() as w:
        got = other.predict(X)
    assert (w.hit, w.built) == (0, 2)
    assert got.tobytes() == want.tobytes()
    assert len(model._forest_chunks) == 2          # the source keeps its own
    with _watch() as w:
        model.predict(X)
    assert w.built == 0


# -- two callers at once --------------------------------------------------
def test_two_threads_score_one_model(fitted):
    """`serve/` scores one model from several threads: whoever misses
    builds, every answer is the cold model's, and once the threads have
    met the forest is kept."""
    model, X = _forest(fitted, "binary", 100)
    want = _cold(model).predict(X).tobytes()
    model.predict(X[:8])                   # compile outside the race
    model._forest_chunks = ()
    start = threading.Barrier(4)
    wrong, errors = [], []

    def score():
        try:
            start.wait()
            for i in range(20):
                if model.predict(X).tobytes() != want:
                    wrong.append(i)
        except BaseException as exc:       # noqa: BLE001 (reported below)
            errors.append(exc)

    with _watch() as w:
        threads = [threading.Thread(target=score) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errors and not wrong
    assert len(w.spans) == 80 and w.hit + w.built == 160
    assert 2 <= w.built <= 8               # at most once a thread
    assert len(model._forest_chunks) == 2
    with _watch() as w:
        model.predict(X)
    assert (w.hit, w.built) == (2, 0)


def test_growing_forest_under_a_scoring_thread(fitted):
    """The stream refresh appends while `serve/` scores: every answer is
    that of some prefix the list has had."""
    model, X = _forest(fitted, "binary", 60)
    extra = [_fresh(model.trees[i % 3]) for i in range(10)]
    cold = _cold(model)
    cold.trees = cold.trees + [_fresh(t) for t in extra]
    allowed = {cold.predict(X, n_trees=k).tobytes() for k in range(60, 71)}
    seen, stop = [], threading.Event()

    def score():
        while not stop.is_set():
            seen.append(model.predict(X).tobytes())

    t = threading.Thread(target=score)
    t.start()
    try:
        for tree in extra:
            model.trees.append(tree)
            n = len(seen)
            while len(seen) < n + 2 and t.is_alive():
                pass
    finally:
        stop.set()
        t.join()
    assert seen and set(seen) <= allowed
    assert model.predict(X).tobytes() == cold.predict(X).tobytes()


# -- the compiled programs are the ones they were --------------------------
def test_no_new_program_for_a_kept_forest(fitted):
    """A hit hands the slab program (`_predict_slab`, the one program
    `predict` runs) arrays of the shapes a build does: nothing compiles
    on the second call, nor for a prefix of as many chunks."""
    model, X = _forest(fitted, "binary", 100)
    model.predict(X)
    before = G._predict_slab._cache_size()
    model.predict(X)
    model.predict(X, n_trees=70)
    assert G._predict_slab._cache_size() == before
