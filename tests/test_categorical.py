"""Native categorical features (ISSUE 58): ``feature_types``,
``max_cat_to_onehot``, ``max_cat_threshold`` on the normal path —
``HistGBT(...)`` → ``make_device_data`` → ``fit_device`` → ``predict`` /
``predict_leaf`` / ``save_model`` / ``load_model`` — held to the plain
reference (``benchmark/reference_cat.py``, float64, the published rule) on
seeded data, at toy sizes on the CPU.

The first tree's gradients are +-0.5 and 0.25, which float32 sums hold
exactly at these sizes: the program's splits, sets and leaves have to be
the reference's, array for array.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import datagen_cat  # noqa: E402
from benchmark import reference as ref  # noqa: E402
from benchmark import reference_cat as rc  # noqa: E402
from dmlc_core_tpu.base.logging import Error  # noqa: E402
from dmlc_core_tpu.io.stream import Stream  # noqa: E402
from dmlc_core_tpu.models import HistGBT, SparseHistGBT  # noqa: E402
from dmlc_core_tpu.ops import quantile as Q  # noqa: E402
from dmlc_core_tpu.ops import table_select as TS  # noqa: E402
from dmlc_core_tpu.parallel.mesh import local_mesh  # noqa: E402

CFG = dict(max_depth=3, n_bins=256, learning_rate=0.3, reg_lambda=1.0,
           min_child_weight=5.0, max_cat_to_onehot=4, max_cat_threshold=64,
           base_score=0.0)


def _table(n, cards, seed, numeric=1):
    """``n`` rows of categorical columns of ``cards`` levels (codes drawn
    with seeded skew) and ``numeric`` gaussian ones, the label a Bernoulli
    of seeded per-category effects: no interval of codes is the best set."""
    rng = np.random.default_rng(seed)
    cols, logit = [], np.zeros(n)
    for c in cards:
        w = rng.random(c) ** 2 + 0.02
        code = rng.choice(c, n, p=w / w.sum())
        logit += rng.normal(0, 1.0, c)[code]
        cols.append(code)
    for _ in range(numeric):
        q = rng.normal(size=n)
        logit += 0.4 * q
        cols.append(q)
    X = np.stack(cols, 1).astype(np.float32)
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return X, y, ["c"] * len(cards) + ["q"] * numeric


def _fit(X, y, types, n_trees=2, mesh=None, **over):
    cfg = dict(CFG, **over)
    model = HistGBT(n_trees=n_trees, objective="binary:logistic", mesh=mesh,
                    feature_types=types, **cfg)
    handle = model.make_device_data(X, y)
    model.fit_device(handle)
    return model, handle, dict(cfg, feature_types=types)


def _host(trees):
    return [{k: np.asarray(v) for k, v in t.items()} for t in trees]


def _reference_tree(model, handle, y, cfg):
    cuts = np.asarray(model.cuts)
    used = rc.used_bins(cuts, cfg["feature_types"])
    bins_t = np.asarray(handle["bins_t"])[:, :len(y)]
    g, h = ref.logistic_grad_hess(np.zeros(len(y)), y.astype(np.float64))
    return rc.grow(bins_t, g, h, used, cfg), bins_t, used


# -- the first tree is the reference's, one case a rule -----------------------

RULES = {
    # a 40-level column: the sorted-histogram partition
    "partition": dict(cards=(40,), numeric=1),
    # three levels: each single bin against the rest
    "one_vs_rest": dict(cards=(3, 4), numeric=0),
    # 120 levels and a limit of 8 bins on the smaller side
    "threshold_binds": dict(cards=(120,), numeric=0, max_cat_threshold=8),
    # 300 levels: 255 names, the rarest share "other"
    "overflow_into_other": dict(cards=(300,), numeric=1, n=30000),
    # numeric and categorical columns in one argmax
    "mixed": dict(cards=(12, 31), numeric=3),
}


@pytest.mark.parametrize("rule", sorted(RULES))
def test_first_tree_is_the_references(rule):
    spec = dict(RULES[rule])
    n = spec.pop("n", 6000)
    X, y, types = _table(n, spec.pop("cards"), 58, spec.pop("numeric"))
    model, handle, cfg = _fit(X, y, types, **spec)
    want, bins_t, used = _reference_tree(model, handle, y, cfg)
    got = _host(model.trees)[0]
    cuts = np.asarray(model.cuts)
    # tables by falling count, bins by lookup
    for f, t in enumerate(types):
        if t == "c":
            assert np.array_equal(rc.cat_table(X[:, f], 256), cuts[f])
    assert np.array_equal(rc.bin_rows(X, cuts, types).T, bins_t)
    for key in ("feat", "thr", "cats"):
        assert np.array_equal(got[key], want[key]), key
    np.testing.assert_allclose(got["gain"], want["gain"], rtol=2e-5,
                               atol=1e-4)
    np.testing.assert_allclose(got["leaf"], want["leaf"], rtol=1e-5,
                               atol=1e-7)
    cat_nodes = [(lv, i) for lv in range(3) for i in range(1 << lv)
                 if got["thr"][lv, i] < 255 and used[got["feat"][lv, i]]]
    assert cat_nodes, "no categorical split: the case tests nothing"
    sizes = [int(rc.set_members(got["cats"][lv, i], 256).sum())
             for lv, i in cat_nodes]
    assert all(got["thr"][lv, i] == s - 1
               for (lv, i), s in zip(cat_nodes, sizes))
    assert max(sizes) <= cfg["max_cat_threshold"]
    if rule == "one_vs_rest":
        assert set(sizes) == {1}
    if rule == "threshold_binds":
        assert max(sizes) == 8
    if rule == "overflow_into_other":
        assert used[0] == 256 and (cuts[0] >= 0).sum() == 255
        assert (bins_t[0] == 255).any()


def test_a_partition_no_threshold_can_express_is_found():
    """Codes whose effect alternates: the root's set is no interval of
    codes and its gain beats the best threshold on the codes read as an
    order (the same rows fitted with every column numeric)."""
    rng = np.random.default_rng(3)
    n = 8000
    code = rng.integers(0, 16, n)
    p = np.where(code % 2 == 0, 0.8, 0.2)
    y = (rng.random(n) < p).astype(np.float32)
    X = code[:, None].astype(np.float32)
    cat, _, _ = _fit(X, y, ["c"], n_trees=1)
    num, _, _ = _fit(X, y, [], n_trees=1)
    t = _host(cat.trees)[0]
    table = np.asarray(cat.cuts)[0]
    left = np.sort(table[np.flatnonzero(
        rc.set_members(t["cats"][0, 0], 256))].astype(int))
    assert len(left) == 8 and len({c % 2 for c in left}) == 1
    assert np.any(np.diff(left) > 1)             # not an interval of codes
    assert t["gain"][0, 0] > 3 * _host(num.trees)[0]["gain"][0, 0]
    assert "cats" not in num.trees[0]


# -- scoring --------------------------------------------------------------------

@pytest.fixture(scope="module")
def fitted():
    X, y, types = _table(5000, (12, 300), 7, numeric=2)
    model, handle, cfg = _fit(X, y, types, n_trees=4)
    return X, y, types, model, cfg


def test_predict_and_predict_leaf_agree_with_the_reference(fitted):
    X, y, types, model, cfg = fitted
    cuts, trees = np.asarray(model.cuts), _host(model.trees)
    want = rc.ensemble_margin(X[:800], cuts, types, trees, 0.0, 256)
    got = model.predict(X[:800], output_margin=True)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    np.testing.assert_allclose(model.train_margins()[:800], want, atol=1e-6)
    bins_t = np.ascontiguousarray(rc.bin_rows(X[:800], cuts, types).T)
    leaves = np.stack([rc.descend_binned(bins_t, t, 256) for t in trees], 1)
    assert np.array_equal(model.predict_leaf(X[:800]), leaves)


def test_an_unseen_code_scores_as_other(fitted):
    X, y, types, model, cfg = fitted
    seen = X[:64].copy()
    unseen = seen.copy()
    unseen[:, 0] = 999.0            # a 12-level column: "other" holds no row
    unseen[:, 1] = 5000.0           # a 300-level column: "other" holds the rare
    rare = np.setdiff1d(np.arange(300), np.asarray(model.cuts)[1])
    as_rare = seen.copy()
    as_rare[:, 0] = 999.0
    as_rare[:, 1] = rare[0]
    assert np.array_equal(model.predict(unseen), model.predict(as_rare))
    want = rc.ensemble_margin(unseen, np.asarray(model.cuts), types,
                              _host(model.trees), 0.0, 256)
    np.testing.assert_allclose(model.predict(unseen, output_margin=True),
                               want, atol=1e-6)


def test_save_load_predict(fitted, tmp_path):
    X, y, types, model, cfg = fitted
    uri = str(tmp_path / "cat.model")
    model.save_model(uri)
    back = HistGBT.load_model(uri)
    assert list(back.param.feature_types) == types
    assert (back.param.max_cat_to_onehot, back.param.max_cat_threshold) == (
        4, 64)
    assert np.array_equal(back.predict(X[:500]), model.predict(X[:500]))
    assert np.array_equal(back.predict_leaf(X[:50]),
                          model.predict_leaf(X[:50]))
    assert back._cat_bins() == model._cat_bins()
    # and the text dump names the codes of a set
    dump = model.dump_model(with_stats=True)
    assert ":{" in dump and "gain=" in dump
    imp = model.feature_importances("gain")
    assert imp[:2].sum() > 0


def test_a_model_file_written_before_this_pr_loads(tmp_path):
    """A payload without the three parameters and without ``cats`` (what
    ``save_model`` wrote until now) loads and scores as it did."""
    from dmlc_core_tpu.io.serializer import read_obj, write_obj

    X, y, _ = _table(2000, (), 1, numeric=4)
    model = HistGBT(n_trees=3, max_depth=3, n_bins=32).fit(X, y)
    uri = str(tmp_path / "new.model")
    model.save_model(uri)
    s = Stream.create(uri, "r")
    magic = s.read(len(HistGBT._MODEL_MAGIC))
    payload = read_obj(s)
    s.close()
    for key in ("feature_types", "max_cat_to_onehot", "max_cat_threshold"):
        assert key in payload["param"]
        del payload["param"][key]
    old = str(tmp_path / "old.model")
    s = Stream.create(old, "w")
    s.write(bytes(magic))
    write_obj(s, payload)
    s.close()
    back = HistGBT.load_model(old)
    assert list(back.param.feature_types) == []
    assert np.array_equal(back.predict(X), model.predict(X))


# -- a mesh ---------------------------------------------------------------------

def test_a_four_device_mesh_equals_one_device():
    X, y, types = _table(6002, (12, 300), 11, numeric=1)
    one, h1, _ = _fit(X, y, types, mesh=local_mesh(1))
    four, h4, _ = _fit(X, y, types, mesh=local_mesh(4))
    assert four.round_plan["mesh_devices"] == 4
    assert np.array_equal(np.asarray(one.cuts), np.asarray(four.cuts))
    assert np.array_equal(np.asarray(h1["bins_t"])[:, :len(y)],
                          np.asarray(h4["bins_t"])[:, :len(y)])
    a, b = _host(one.trees), _host(four.trees)
    for key in ("feat", "thr", "cats"):
        assert np.array_equal(a[0][key], b[0][key]), key
    np.testing.assert_allclose(a[0]["leaf"], b[0]["leaf"], atol=1e-6)
    np.testing.assert_allclose(four.predict(X[:300]), one.predict(X[:300]),
                               atol=1e-5)


def test_multiclass_trees_carry_a_class_axis():
    X, y, types = _table(3000, (10,), 5, numeric=1)
    y3 = (X[:, 0] % 3).astype(np.float32)
    model = HistGBT(n_trees=2, objective="multi:softmax", max_depth=2,
                    feature_types=types)
    model.fit(X, y3)
    assert np.asarray(model.trees[0]["cats"]).shape == (3, 2, 2, 8)
    assert (model.predict(X) == y3).mean() > 0.95


# -- what is refused, each by its message -----------------------------------------

def _refused(match, fn):
    with pytest.raises(Error, match=match):
        fn()


X0, Y0, T0 = _table(600, (6,), 0, numeric=1)


def _nan_beside():
    X = X0.copy()
    X[3, 1] = np.nan
    HistGBT(n_trees=1, feature_types=T0).make_device_data(X, Y0)


def _bad_codes(value):
    def run():
        X = X0.copy()
        X[5, 0] = value
        HistGBT(n_trees=1, feature_types=T0).make_device_data(X, Y0)
    return run


def _packed(monkeypatch_env):
    def run():
        os.environ[monkeypatch_env] = "1"
        try:
            X = np.stack([X0[:, 0] % 3, X0[:, 0] % 2], 1)
            HistGBT(n_trees=1, feature_types=["c", "c"]).fit(X, Y0)
        finally:
            del os.environ[monkeypatch_env]
    return run


def _host_binning():
    os.environ["DMLC_TPU_BIN_BACKEND"] = "cpu"
    try:
        HistGBT(n_trees=1, feature_types=T0).make_device_data(X0, Y0)
    finally:
        del os.environ["DMLC_TPU_BIN_BACKEND"]


def _iter_ingest():
    m = HistGBT(n_trees=1, feature_types=T0)
    m.make_device_data_iter(lambda: iter(()), 2)


REFUSALS = {
    "lossguide": ("lossguide.*categorical", lambda: HistGBT(
        n_trees=1, feature_types=T0, grow_policy="lossguide",
        max_leaves=4).fit(X0, Y0)),
    "monotone": ("monotone_constraints with categorical", lambda: HistGBT(
        n_trees=1, feature_types=T0,
        monotone_constraints=[0, 1]).fit(X0, Y0)),
    "nan_beside_a_category": ("NaN and feature_types", _nan_beside),
    "nan_code": ("NaN and feature_types", _bad_codes(np.nan)),
    "negative_code": ("negative, fractional or NaN", _bad_codes(-1.0)),
    "fractional_code": ("negative, fractional or NaN", _bad_codes(2.5)),
    "an_id_not_a_category": ("ids, not categories", _bad_codes(70000.0)),
    "packed_layout": ("DMLC_BIN_PACK / DMLC_FEATURE_BUNDLE",
                      _packed("DMLC_BIN_PACK")),
    "iter_ingest": ("make_device_data_iter.*categorical", _iter_ingest),
    "fit_external": ("fit_external: categorical", lambda: HistGBT(
        n_trees=1, feature_types=T0).fit_external(None)),
    "sparse_engine": ("SparseHistGBT: categorical", lambda: SparseHistGBT(
        n_trees=1, feature_types=T0)),
    "wrong_length": ("feature_types length", lambda: HistGBT(
        n_trees=1, feature_types=["c"]).fit(X0, Y0)),
    "unknown_type": ("'q' \\(numeric\\) and 'c'", lambda: HistGBT(
        n_trees=1, feature_types=["c", "x"])),
    "host_binning": ("DMLC_TPU_BIN_BACKEND=cpu bins against cut points",
                     _host_binning),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_refused(what):
    _refused(*REFUSALS[what])


# -- no categorical column: the programs of before ---------------------------------

def test_no_c_column_traces_the_parents_round_program():
    """``feature_types`` all ``"q"`` and ``feature_types`` unset are one
    plan, one cache key and one jaxpr: nothing categorical is traced."""
    import jax

    X, y, _ = _table(900, (), 2, numeric=3)
    plain = HistGBT(n_trees=2, max_depth=3, n_bins=32)
    typed = HistGBT(n_trees=2, max_depth=3, n_bins=32,
                    feature_types=["q", "q", "q"])
    pa, pb = plain._round_plan(3, 900), typed._round_plan(3, 900)
    assert pa == pb and pa.cat_bins == () and pa.cat_words == 0
    assert "cat_features" not in plain.round_plan
    assert plain._round_fn_cache_key(pa, 2) == typed._round_fn_cache_key(pb, 2)
    plain.fit(X, y)
    typed.fit(X, y)
    for a, b in zip(plain.trees, typed.trees):
        assert set(a) == set(b) == {"feat", "thr", "gain", "leaf"}
        assert all(np.array_equal(a[k], b[k]) for k in a)
    text = str(jax.make_jaxpr(lambda x, c: Q.apply_bins_t(x, c))(
        X, np.asarray(plain.cuts)))
    assert text == str(jax.make_jaxpr(
        lambda x, c: Q.apply_bins_t(x, c, cat=(False,) * 3))(
            X, np.asarray(plain.cuts)))


# -- the pieces ------------------------------------------------------------------

def test_cat_tables_rule_ties_and_overflow():
    x = np.zeros((10, 2), np.float32)
    x[:, 0] = [5, 5, 5, 2, 2, 9, 9, 7, 1, 1]     # 5:3, 1/2/9:2, 7:1
    x[:, 1] = 0.5
    t = np.asarray(Q.cat_tables(x, (True, False), 10, 4))
    assert t.tolist() == [[5.0, 1.0, 2.0]]       # ties to the lower code
    bins = np.asarray(Q.apply_bins_t(
        x, np.concatenate([t, np.zeros((1, 3), np.float32)]),
        cat=(True, False)))
    assert bins[0].tolist() == [0, 0, 0, 2, 2, 3, 3, 3, 1, 1]  # 9, 7: other
    assert Q.cat_bins_used(np.concatenate([t, t * 0]), (True, False)) == (4, 0)
    assert Q.cat_bins_used(np.array([[3.0, 1.0, -1.0]]), (True,)) == (2,)


@pytest.mark.parametrize("nodes", [10, 70])
def test_set_select_is_membership(nodes):
    """Both forms — one table up to ``SET_FLAT_MAX`` words in all, a word
    at a time past it — answer membership, pad rows and bins past the
    last word included."""
    rng = np.random.default_rng(0)
    member = rng.random((nodes, 256)) < 0.3
    words = TS.set_words(member)
    assert np.array_equal(np.asarray(words), rc.set_words(member))
    assert (nodes * 8 <= TS.SET_FLAT_MAX) == (nodes == 10)
    node = rng.integers(-1, nodes, 5000).astype(np.int32)
    row_bin = rng.integers(0, 256, 5000).astype(np.int32)
    got = np.asarray(TS.set_select(words, node, row_bin, nodes))
    want = np.where(node >= 0, member[np.maximum(node, 0), row_bin], False)
    assert np.array_equal(got, want)
    # fewer words than bins: a bin past the last word is in no set
    got = np.asarray(TS.set_select(words[:, :2], node, row_bin, nodes))
    assert np.array_equal(got, want & (row_bin < 64))


def test_the_benchmarks_rows_are_the_sources_shapes():
    X, y = datagen_cat.airline_like(20000, 2**31 + 58)
    assert X.shape == (20000, 8) and X.dtype == np.float32
    for f, c in enumerate(datagen_cat.CARDINALITIES):
        if c:
            assert X[:, f].min() >= 0 and X[:, f].max() < c
            assert np.array_equal(X[:, f], np.floor(X[:, f]))
    assert 0.1 < y.mean() < 0.35
    again = datagen_cat.airline_like(20000, 2**31 + 58)
    assert np.array_equal(X, again[0]) and np.array_equal(y, again[1])
    assert "dmlc_core_tpu" not in open(rc.__file__).read()


def test_the_sklearn_wrapper_passes_the_parameters_through():
    from dmlc_core_tpu.models.sklearn import GBTClassifier

    X, y, types = _table(3000, (16,), 9, numeric=1)
    clf = GBTClassifier(n_estimators=3, max_depth=3, feature_types=types,
                        max_cat_threshold=5)
    clf.fit(X, y)
    assert clf.get_params()["feature_types"] == types
    assert "cats" in clf.model.trees[0]
    assert clf.model.param.max_cat_threshold == 5
    assert clf.predict_proba(X[:10]).shape == (10, 2)
