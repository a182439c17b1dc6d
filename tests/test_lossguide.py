"""Leaf-wise (lossguide) tree growth, on the ``Parameter`` (ISSUE 56;
the ISSUE 12 oracle contracts kept).

The oracle: with the leaf budget of a full tree, gain-priority leaf-wise
expansion visits exactly the set of nodes depth-wise growth splits
(every split it records has gain > gamma, and expansion order cannot
change which splits are profitable), and the single-node histogram
builds are bit-identical to the level-batched ones — so tree STRUCTURE
(feat/thr) must match depth-wise exactly.  Leaf values may differ at
last-ulp in UNREACHABLE leaves: depth-wise materializes a degenerate
right-subtraction chain under pruned nodes (hist − hist of identical row
sets is not exactly 0 after the parent was itself subtracted), where a
node list simply has no such leaf; no rows reach those leaves, so
predictions agree to float tolerance.

A loss-guide tree is a node list of ``2 * max_leaves - 1`` entries of any
depth; against ``benchmark/reference_lossguide.py`` (float64, the
published rule) it has the same expansion order, splits and leaf values
wherever the gradients are dyadic (sums exact in float32).
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import reference as ref  # noqa: E402
from benchmark import reference_lossguide as rl  # noqa: E402
from dmlc_core_tpu.base.logging import Error  # noqa: E402
from dmlc_core_tpu.models import HistGBT  # noqa: E402
from dmlc_core_tpu.models import histgbt as hg  # noqa: E402
from dmlc_core_tpu.ops.histogram import leaves_built_per_round  # noqa: E402
from dmlc_core_tpu.parallel.mesh import local_mesh  # noqa: E402

KW = dict(n_trees=4, max_depth=4, n_bins=32,
          objective="binary:logistic", learning_rate=0.3)
LG = dict(grow_policy="lossguide")


def _xy(n=2003, F=7, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    X[:, 2] = rng.integers(0, 3, n)
    y = ((X[:, 0] + 0.5 * X[:, 2] - X[:, 1] * X[:, 3]) > 0
         ).astype(np.float32)
    return X, y


def _to_levels(tree, depth, n_bins):
    """A node list in depth-wise's complete-binary arrays: node (level,
    index) at ``[level, index]``, a leaf's value at its leftmost
    depth-level descendant."""
    half = 1 << (depth - 1)
    feat = np.zeros((depth, half), np.int32)
    thr = np.zeros((depth, half), np.int32)
    gain = np.zeros((depth, half), np.float32)
    for lv in range(depth):
        thr[lv, :1 << lv] = n_bins - 1               # degenerate: all left
    leaf = np.zeros(1 << depth, np.float32)
    todo = [(0, 0, 0)]
    while todo:
        i, lv, j = todo.pop()
        if tree["left"][i] > 0:
            feat[lv, j], thr[lv, j] = tree["feat"][i], tree["thr"][i]
            gain[lv, j] = tree["gain"][i]
            todo += [(int(tree["left"][i]), lv + 1, 2 * j),
                     (int(tree["right"][i]), lv + 1, 2 * j + 1)]
        else:
            leaf[j << (depth - lv)] = tree["value"][i]
    return {"feat": feat, "thr": thr, "gain": gain, "leaf": leaf}


def _bins_t(model, X):
    return ref.bin_rows(X, np.asarray(model.cuts)).T.astype(np.uint8)


class TestLossguideOracle:
    def test_unlimited_budget_matches_depthwise(self):
        X, y = _xy()
        m0 = HistGBT(**KW)
        m0.fit(X, y)
        m1 = HistGBT(**KW, **LG)
        m1.fit(X, y)
        assert m1.round_plan["max_leaves"] == 16
        for i, (t0, t1) in enumerate(zip(m0.trees, m1.trees)):
            assert sorted(t1) == sorted(rl.KEYS) and len(t1["left"]) == 31
            t1 = _to_levels(t1, KW["max_depth"], KW["n_bins"])
            assert np.array_equal(t0["feat"], t1["feat"]), i
            assert np.array_equal(t0["thr"], t1["thr"]), i
            np.testing.assert_allclose(t0["gain"], t1["gain"],
                                       rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(t0["leaf"], t1["leaf"],
                                       rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(m0.predict(X), m1.predict(X),
                                   rtol=1e-5, atol=1e-6)

    def test_max_leaves_budget_respected(self):
        X, y = _xy(seed=1)
        m = HistGBT(max_leaves=6, **KW, **LG)
        m.fit(X, y)
        for t in m.trees:
            # 2 * max_leaves - 1 entries, <= max_leaves − 1 of them split
            assert len(t["left"]) == 11
            assert int((np.asarray(t["left"]) > 0).sum()) <= 5
            assert len(rl.leaves_of(t)) <= 6
            assert rl.depth_of(t) <= KW["max_depth"]   # the cap holds too
        acc = ((m.predict(X) > 0.5) == y).mean()
        assert acc > 0.8

    def test_default_policy_is_depthwise_byte_parity(self, tmp_path):
        X, y = _xy(seed=2)
        m0 = HistGBT(**KW)
        m0.fit(X, y)
        m1 = HistGBT(grow_policy="depthwise", **KW)
        m1.fit(X, y)
        u0, u1 = str(tmp_path / "a.ubj"), str(tmp_path / "b.ubj")
        m0.save_model(u0)
        m1.save_model(u1)
        assert open(u0, "rb").read() == open(u1, "rb").read()

    def test_invalid_policy_rejected(self):
        with pytest.raises(Error, match="grow_policy"):
            HistGBT(grow_policy="bogus", **KW)

    def test_packed_lossguide_structure(self, monkeypatch):
        # both levers together: packed storage + leaf-wise growth
        X, y = _xy(seed=3)
        X[:, 5] = np.random.default_rng(3).integers(0, 4, len(X))
        m0 = HistGBT(**KW)
        m0.fit(X, y)
        monkeypatch.setenv("DMLC_BIN_PACK", "1")
        m1 = HistGBT(**KW, **LG)
        m1.fit(X, y)
        assert m1._bin_layout is not None
        for t0, t1 in zip(m0.trees, m1.trees):
            t1 = _to_levels(t1, KW["max_depth"], KW["n_bins"])
            assert np.array_equal(t0["feat"], t1["feat"])
            assert np.array_equal(t0["thr"], t1["thr"])


# ----------------------------------------------------------------------
# against the plain reference, on dyadic gradients
# ----------------------------------------------------------------------

def _reference_tree(model, X, g, h):
    p = model.param
    return rl.grow(_bins_t(model, X), g, h, p.n_bins,
                   model.round_plan["max_leaves"], p.reg_lambda,
                   p.min_child_weight, p.learning_rate, p.gamma,
                   p.max_depth)


def _same_tree(got, want, rtol=2e-6):
    """Same expansion order (the node ids say it), splits and values."""
    assert len(got["left"]) == len(want["left"])
    for k in ("left", "right", "feat", "thr"):
        assert np.array_equal(got[k], want[k]), k
    # (a gain is a difference of terms as large as the root's)
    np.testing.assert_allclose(got["gain"], want["gain"], rtol=1e-5,
                               atol=1e-6 * float(np.max(want["gain"])))
    np.testing.assert_allclose(got["value"], want["value"], rtol=rtol,
                               atol=1e-7)


_CASES = {
    # tree 0 of a logistic fit: g = +-0.5, h = 0.25
    "logistic_l12": (dict(objective="binary:logistic", max_depth=0,
                          max_leaves=12), 0),
    "logistic_l24_mcw": (dict(objective="binary:logistic", max_depth=0,
                              max_leaves=24, min_child_weight=16.0), 1),
    "logistic_depth_cap": (dict(objective="binary:logistic", max_depth=3,
                                max_leaves=12), 2),
    # squared error on small-integer labels: g = -y, h = 1
    "squared_l16_gamma": (dict(objective="reg:squarederror", max_depth=0,
                               max_leaves=16, gamma=40.0), 3),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_first_tree_is_the_references(case):
    kw, seed = _CASES[case]
    X, y = _xy(n=1600, seed=seed)
    if kw["objective"] == "reg:squarederror":
        y = np.round(4 * X[:, 0] + X[:, 2]).astype(np.float32)
        g, h = -y.astype(np.float64), np.ones(len(y))
    else:
        g, h = np.where(y > 0.5, -0.5, 0.5), np.full(len(y), 0.25)
    m = HistGBT(n_trees=1, n_bins=32, learning_rate=0.5, **kw, **LG)
    m.fit(X, y)
    (tree,) = m.trees
    L = m.round_plan["max_leaves"]
    assert L == (8 if case == "logistic_depth_cap" else kw["max_leaves"])
    assert len(tree["left"]) == 2 * L - 1
    want = _reference_tree(m, X, g, h)
    _same_tree(tree, want)
    # the case bites: the budget is used up, or min_child_weight, gamma or
    # the cap stopped the tree short of it
    grown = len(rl.leaves_of(tree))
    if case in ("logistic_l24_mcw", "squared_l16_gamma"):
        assert 4 <= grown < L, grown
    else:
        assert grown == L, grown
    if kw["max_depth"]:
        assert rl.depth_of(tree) <= kw["max_depth"]
    # the device walker lands every row where the reference's descent does
    leaf = m.predict_leaf(X)[:, 0]
    assert np.array_equal(leaf, rl.descend_binned(_bins_t(m, X), tree))
    np.testing.assert_allclose(
        m.predict(X, output_margin=True),
        rl.ensemble_margin(X, np.asarray(m.cuts), [want], 0.0), rtol=1e-5,
        atol=1e-6)


def _skewed(buckets=16, per=8):
    """One column of ``buckets`` values, the label 3**bucket: the best
    split always peels the top bucket off, so the tree is a chain."""
    x = np.repeat(np.arange(buckets), per).astype(np.float32)
    rng = np.random.default_rng(4)
    X = np.stack([x, rng.normal(size=len(x)).astype(np.float32)], axis=1)
    return X, (3.0 ** x).astype(np.float32)


def test_a_tree_deeper_than_12_grows_saves_loads_and_predicts(tmp_path):
    X, y = _skewed()
    m = HistGBT(n_trees=2, n_bins=32, learning_rate=0.5, max_depth=0,
                max_leaves=16, objective="reg:squarederror", **LG)
    m.fit(X, y)
    tree = m.trees[0]
    assert rl.depth_of(tree) == 15 > 12
    # (sums near 3**15 are not exact in float32: a looser value)
    _same_tree(tree, _reference_tree(m, X, -y.astype(np.float64),
                                     np.ones(len(y))), rtol=1e-5)
    uri = str(tmp_path / "deep.ubj")
    m.save_model(uri)
    back = HistGBT.load_model(uri)
    assert (back.param.grow_policy, back.param.max_leaves,
            back.param.max_depth) == ("lossguide", 16, 0)
    for t0, t1 in zip(m.trees, back.trees):
        assert sorted(t0) == sorted(t1) == sorted(rl.KEYS)
        assert all(np.array_equal(t0[k], t1[k]) for k in t0)
    got = back.predict(X)
    assert np.array_equal(got, m.predict(X))
    np.testing.assert_allclose(
        got, rl.ensemble_margin(X, np.asarray(m.cuts), m.trees, 0.0),
        rtol=1e-5)
    np.testing.assert_allclose(m.train_margins(), got, rtol=1e-6)
    assert np.array_equal(
        back.predict_leaf(X),
        np.stack([rl.descend_binned(_bins_t(m, X), t) for t in m.trees], 1))


def test_min_child_weight_is_honoured():
    X, y = _xy(n=3000, seed=5)
    m = HistGBT(n_trees=1, n_bins=32, max_depth=0, max_leaves=32,
                min_child_weight=25.0, **LG)
    m.fit(X, y)
    (tree,) = m.trees
    g, h = np.where(y > 0.5, -0.5, 0.5), np.full(len(y), 0.25)
    rep = rl.replay(_bins_t(m, X), g, h, tree, 32, 1.0, 25.0)
    children = np.concatenate([tree["left"][rep["order"]],
                               tree["right"][rep["order"]]])
    assert len(children) >= 8 and rep["H"][children].min() >= 25.0
    # ... and it is what stopped the tree short of its budget
    assert len(rep["leaves"]) < 32
    loose = HistGBT(n_trees=1, n_bins=32, max_depth=0, max_leaves=32, **LG)
    loose.fit(X, y)
    assert len(rl.leaves_of(loose.trees[0])) == 32


def _never_build(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("a round program was built")
    monkeypatch.setattr(hg.HistGBT, "_build_round_fn", refuse)


_REFUSED = {
    "missing": (dict(max_leaves=8, **LG), "NaN/missing"),
    "monotone": (dict(max_leaves=8, monotone_constraints=(1,) + (0,) * 6,
                      **LG), "monotone_constraints"),
    "depthwise_without_depth": (dict(max_depth=0), "max_depth=0"),
    "lossguide_without_bound": (dict(max_depth=0, **LG), "needs a bound"),
    "one_leaf": (dict(max_leaves=1, **LG), ">= 2 leaves"),
}


@pytest.mark.parametrize("case", sorted(_REFUSED))
def test_refused_where_the_plan_is_made(case, monkeypatch):
    kw, message = _REFUSED[case]
    X, y = _xy(n=400, seed=6)
    if case == "missing":
        X[::7, 1] = np.nan
    _never_build(monkeypatch)
    m = HistGBT(mesh=local_mesh(1), **dict(KW, **kw))
    with pytest.raises(Error, match=message):
        m.fit_device(m.make_device_data(X, y))


def test_the_sparse_engine_refuses_the_policy():
    from dmlc_core_tpu.models.histgbt_sparse import SparseHistGBT

    with pytest.raises(Error, match="depth-wise trees only"):
        SparseHistGBT(max_leaves=8, **LG)
    with pytest.raises(Error, match="depth-wise trees only"):
        SparseHistGBT(max_depth=0)


def test_a_model_file_written_before_the_parameters_loads(tmp_path):
    """A file of the parent: its ``param`` has neither ``grow_policy``
    nor ``max_leaves``, its trees are depth-wise's arrays."""
    from dmlc_core_tpu.io.serializer import write_obj
    from dmlc_core_tpu.io.stream import Stream

    X, y = _xy(seed=7)
    m = HistGBT(**KW)
    m.fit(X, y)
    param = m.param.to_dict()
    assert param.pop("grow_policy") == "depthwise"
    assert param.pop("max_leaves") == 0
    uri = str(tmp_path / "old.ubj")
    s = Stream.create(uri, "w")
    s.write(HistGBT._MODEL_MAGIC)
    write_obj(s, {"param": param, "cuts": np.asarray(m.cuts),
                  "trees": m.trees, "best_iteration": None,
                  "best_score": None, "early_stopped": False,
                  "missing": False})
    s.close()
    back = HistGBT.load_model(uri)
    assert back.param.grow_policy == "depthwise"
    assert np.array_equal(back.predict(X), m.predict(X))


class TestNodeListForest:
    @pytest.fixture(scope="class")
    def fitted(self):
        X, y = _xy(seed=8)
        m = HistGBT(n_trees=3, n_bins=32, max_depth=0, max_leaves=10, **LG)
        m.fit(X, y)
        return m, X, y

    def test_dump_prints_the_node_list(self, fitted):
        m, _, _ = fitted
        text = m.dump_model(with_stats=True)
        assert text.count("booster[") == 3
        tree = m.trees[0]
        lines = text.split("booster[1]:")[0].strip().split("\n")[1:]
        assert len(lines) == 2 * len(rl.leaves_of(tree)) - 1
        assert lines[0].startswith("\t0:[f") and "yes=1,no=2,gain=" in lines[0]
        assert sum("leaf=" in ln for ln in lines) == len(rl.leaves_of(tree))

    def test_importances_count_the_split_nodes(self, fitted):
        m, _, _ = fitted
        weight = m.feature_importances("weight")
        gain = m.feature_importances("gain")
        splits = [np.asarray(t["left"]) > 0 for t in m.trees]
        assert weight.sum() == sum(int(s.sum()) for s in splits)
        np.testing.assert_allclose(gain.sum(), sum(
            float(np.asarray(t["gain"])[s].sum())
            for t, s in zip(m.trees, splits)), rtol=1e-6)
        assert weight[0] > 0 and (gain >= 0).all()

    def test_a_prefix_of_the_forest_scores(self, fitted):
        m, X, _ = fitted
        one = m.predict(X, output_margin=True, n_trees=1)
        np.testing.assert_allclose(
            one, rl.ensemble_margin(X, np.asarray(m.cuts), m.trees[:1], 0.0),
            rtol=1e-5, atol=1e-6)
        assert m.predict_leaf(X, n_trees=2).shape == (len(X), 2)

    def test_a_continued_fit_replays_the_node_lists(self, fitted):
        m, X, y = fitted
        more = HistGBT(n_trees=2, n_bins=32, max_depth=0, max_leaves=10,
                       **LG)
        more.cuts, more.trees = m.cuts, list(m.trees)
        more.fit(X, y)
        assert len(more.trees) == 5
        np.testing.assert_allclose(more.train_margins(),
                                   more.predict(X, output_margin=True),
                                   rtol=1e-5, atol=1e-6)

    def test_a_mixed_forest_is_refused(self, fitted):
        m, X, y = fitted
        other = HistGBT(n_trees=1, n_bins=32, max_depth=3)
        other.fit(X, y)
        other.trees = other.trees + m.trees[:1]
        with pytest.raises(Error, match="mixes depth-wise"):
            other.predict(X)


class TestLeavesAccounting:
    def test_leaves_built_per_round(self):
        # depth-wise: root + left children only (sibling subtraction)
        assert leaves_built_per_round(1) == 1
        assert leaves_built_per_round(6) == 32
        # lossguide: one build per expansion, depth-independent
        assert leaves_built_per_round(6, "lossguide", 8) == 8
        assert leaves_built_per_round(6, "lossguide", 0) == 64
        # ... and with no depth cap the budget alone
        assert leaves_built_per_round(0, "lossguide", 255) == 255
