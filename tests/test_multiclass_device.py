"""Several classes on the device path: ``HistGBT(objective="multi:softmax")``
through ``make_device_data`` and ``fit_device`` — K trees a round from one
softmax over a row's K margins, the margins CLASS-MAJOR ``[K, n]`` on the
device and ``[n, K]`` only at the host's edge, the class loop BATCHED
(one ``grow_tree`` with a class axis, a level's K histograms one
``build_histogram`` of K classes).  Held against the benchmark's plain
reference (``benchmark/reference_multi.py``: float64, row by row, no
scan and no class-major layout in it) on seeded rows of whole numbers
with indicator columns, where values sit ON their cuts.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dmlc_core_tpu.models.histgbt as G
from benchmark import checks, reference as ref, reference_multi as rm
from dmlc_core_tpu.base.logging import Error
from dmlc_core_tpu.models import HistGBT
from dmlc_core_tpu.parallel.mesh import local_mesh

K, N, F = 7, 4096, 12
ROUNDS, DEPTH, BINS, ETA, LAM = 3, 3, 32, 0.3, 1.0
PARAMS = dict(n_trees=ROUNDS, max_depth=DEPTH, n_bins=BINS,
              learning_rate=ETA, reg_lambda=LAM)


def _rows(n=N, seed=48):
    """Whole numbers, ties everywhere: four quantitative columns (one of
    255 values, as a hillshade), a one-of-3 and a one-of-5 indicator
    group; seven labels that read the first column's band and both
    groups, with noise."""
    rng = np.random.default_rng(seed)
    y = rng.choice(K, n, p=[.30, .34, .10, .04, .06, .08, .08])
    X = np.zeros((n, F), np.float32)
    X[:, 0] = np.rint(rng.normal(100.0 * (1 + y), 60.0))
    X[:, 1] = rng.integers(0, 255, n)
    X[:, 2] = np.rint(rng.gamma(2.0, 10.0, n))
    X[:, 3] = rng.integers(0, 361, n)
    rows = np.arange(n)
    X[rows, 4 + (y + rng.integers(0, 2, n)) % 3] = 1.0
    X[rows, 7 + (2 * y + rng.integers(0, 3, n)) % 5] = 1.0
    return X, y.astype(np.float32)


def _model(mesh_devices=1, **kw):
    return HistGBT(objective=kw.pop("objective", "multi:softmax"),
                   mesh=local_mesh(mesh_devices), **dict(PARAMS, **kw))


def _host(trees):
    return [{k: np.asarray(v) for k, v in t.items()} for t in trees]


@pytest.fixture(scope="module")
def fitted():
    X, y = _rows()
    model = _model()
    handle = model.make_device_data(X, y)
    model.fit_device(handle)
    return X, y, model, handle, _host(model.trees)


@pytest.fixture(scope="module")
def reference_rounds(fitted):
    """The reference's gradients at the start of rounds 0 and 1: float64
    softmax rows over ``[n, K]`` margins, round 1's from the program's
    own round-0 trees descended plainly, class c's onto column c."""
    X, y, model, handle, trees = fitted
    bins_t = np.asarray(handle["bins_t"])[:, :N]
    margin = np.zeros((N, K))
    out = []
    for k in (0, 1):
        out.append(rm.softmax_grad_hess(margin, y))
        if k == 0:
            for c in range(K):
                t = rm.class_tree(trees[0], c)
                margin[:, c] += np.asarray(t["leaf"], np.float64)[
                    ref.descend_binned(bins_t, t["feat"], t["thr"])]
    return bins_t, out


# -- against the plain reference, round by round, class by class ---------------

@pytest.mark.parametrize("c", range(K))
@pytest.mark.parametrize("k", [0, 1], ids=["round0", "round1"])
def test_a_classes_tree_is_the_references(fitted, reference_rounds, k, c):
    """Class c's tree of round k: its root split reaches the reference's
    best gain on column c of the softmax's gradients, the gain it reports
    is the reference's, and every leaf is ``-eta*G/(H+lambda)`` over the
    rows the tree routes there (float32 sums of 4,096 equal-ish numbers
    against float64: a few 1e-3)."""
    X, y, model, handle, trees = fitted
    bins_t, grads = reference_rounds
    g, h = grads[k]
    t = rm.class_tree(trees[k], c)
    G_, H_ = ref.root_histogram(bins_t, g[:, c], h[:, c], BINS)
    gains = ref.split_gains(G_, H_, LAM, 1.0)
    f0, t0 = int(t["feat"][0, 0]), int(t["thr"][0, 0])
    assert t0 < BINS - 1
    assert (gains.max() - gains[f0, t0]) / gains.max() < 1e-4
    assert abs(float(t["gain"][0, 0]) - gains[f0, t0]) / gains.max() < 1e-4
    node = ref.descend_binned(bins_t, t["feat"], t["thr"])
    leaf = ref.leaf_values(node, g[:, c], h[:, c], 1 << DEPTH, ETA, LAM)
    assert ref.worst_leaf_gap(t["leaf"], leaf) < 2e-2
    # by the rows, the leaves are the reference's to float32
    assert np.abs(np.asarray(t["leaf"], np.float64)[node]
                  - leaf[node]).mean() < 1e-4


def test_the_classes_are_coupled_through_one_softmax(fitted,
                                                     reference_rounds):
    """Round 1 told apart from K one-vs-rest fits: with sigmoids in the
    softmax's place the reference's leaves are not the program's."""
    X, y, model, handle, trees = fitted
    bins_t, grads = reference_rounds
    margin = np.zeros((N, K))
    for c in range(K):
        t = rm.class_tree(trees[0], c)
        margin[:, c] += np.asarray(t["leaf"], np.float64)[
            ref.descend_binned(bins_t, t["feat"], t["thr"])]
    g, h = rm.softmax_grad_hess(margin, y, control="ovr")
    t = rm.class_tree(trees[1], 0)
    node = ref.descend_binned(bins_t, t["feat"], t["thr"])
    leaf = ref.leaf_values(node, g[:, 0], h[:, 0], 1 << DEPTH, ETA, LAM)
    assert ref.worst_leaf_gap(t["leaf"], leaf) > 0.1


def test_binning_puts_a_value_equal_to_a_cut_to_its_right(fitted):
    """Indicator columns have two values and cuts that repeat: the
    program's bins are the number of its own cuts <= x, every row."""
    X, y, model, handle, trees = fitted
    cuts = np.asarray(model.cuts)
    assert checks.bins_mismatches(X, np.asarray(handle["bins_t"])[:, :N],
                                  cuts) == 0
    on_a_cut = (X[:, 4:, None] == cuts[None, 4:, :]).any(axis=2)
    assert on_a_cut.mean() > 0.5           # the ties are there


# -- the batched class loop ------------------------------------------------------

def _written_out(grow, bins_tl, g_all, h_all, feat_mask):
    """The class loop written out: K ``grow``s of ONE class each on the
    static slices ``g_all[c]`` — the program of PR 48's parent."""
    outs = [grow(bins_tl, g_all[c], h_all[c], feat_mask)
            for c in range(g_all.shape[0])]
    return jax.tree.map(lambda *a: jnp.stack(a), *outs)


def _fit(X, y, grow_classes=None, **kw):
    """A fresh fit through a round program traced now, with the class
    loop replaced by ``grow_classes`` where given."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(G, "_ROUND_FN_CACHE", {})
        mp.setattr(G, "_AOT_EXEC_CACHE", {})
        if grow_classes is not None:
            mp.setattr(G, "_grow_classes", grow_classes)
        model = _model(**kw)
        model.fit_device(model.make_device_data(X, y))
    return model


def test_the_batched_class_loop_is_the_loop_written_out(fitted):
    """``round_body`` grows the K trees together, ONE ``grow_tree`` with a
    class axis.  The same round as K ``grow_tree``s of one class written
    out on ``g_all[c]`` gives byte-identical trees and margins."""
    X, y, model, handle, trees = fitted
    loop = _fit(X, y, _written_out)
    assert checks.trees_differ(_host(loop.trees), trees) == 0
    assert np.array_equal(loop.train_margins(), model.train_margins())


@pytest.mark.parametrize("case", [
    "pallas", "pallas_blocks", "lossguide", "lossguide_leaves", "missing",
    "monotone", "sampled", "ten_classes", "det_blocks_mesh2"])
def test_the_batched_loop_is_the_written_out_loop_on_every_path(
        case, monkeypatch):
    """Byte for byte on every path of the round a class axis goes through:
    the stacked Pallas kernel (interpreted; one call a level for the seven
    classes, then in blocks of classes where 7 x A passes 128 rows),
    loss-guide growth (the K trees expanding in step, with and without a
    leaf budget), learned missing directions, monotone bounds handed
    down, row and column sampling (one mask for all classes), more
    classes than one batch holds (ten: two batches of five, scanned), and
    deterministic row blocks on a mesh."""
    n = 1024
    X, y = _rows(n, seed=7)
    kw = dict(n_trees=2)
    if case == "pallas":
        kw.update(hist_method="pallas")            # 32 bins: L2 stacks
    elif case == "pallas_blocks":
        kw.update(hist_method="pallas", n_bins=256, max_depth=5, n_trees=1)
    elif case.startswith("lossguide"):
        kw.update(grow_policy="lossguide")
        if case == "lossguide_leaves":
            kw.update(max_leaves=5)
    elif case == "missing":
        X = X.copy()
        X[::5, 1] = np.nan
        X[1::3, 5] = np.nan
    elif case == "monotone":
        kw.update(monotone_constraints=(1, -1) + (0,) * (F - 2))
    elif case == "sampled":
        kw.update(subsample=0.7, colsample_bytree=0.6, seed=3)
    elif case == "ten_classes":
        y = (y + K * (X[:, 1] > 200)).clip(0, 9).astype(np.float32)
    elif case == "det_blocks_mesh2":
        monkeypatch.setenv("DMLC_HIST_BLOCKS", "4")
        kw.update(mesh_devices=2)
    batched = _fit(X, y, **kw)
    loop = _fit(X, y, _written_out, **kw)
    if case == "ten_classes":
        assert batched.param.num_class == 10
    if case == "pallas_blocks":
        assert batched.round_plan["hist_class_blocks"] == [
            [7], [7], [7], [7], [4, 3]]
    # (a loss-guide tree is a node list: its values are ``value``)
    values = "value" if case.startswith("lossguide") else "leaf"
    assert np.asarray(batched.trees[0][values]).shape[0] == \
        batched.param.num_class
    assert checks.trees_differ(_host(loop.trees), _host(batched.trees)) == 0
    assert np.array_equal(loop.train_margins(), batched.train_margins())
    assert np.abs(batched.train_margins()).max() > 0.1


def test_two_fits_of_one_handle_are_byte_identical(fitted):
    X, y, model, handle, trees = fitted
    model.fit_device(handle)
    assert checks.trees_differ(_host(model.trees), trees) == 0


# -- num_class ----------------------------------------------------------------------

def test_num_class_is_learned_from_the_labels(fitted):
    X, y, model, handle, trees = fitted
    assert model.param.num_class == K               # left unset, learned
    given = _model(num_class=K)
    given.fit_device(given.make_device_data(X, y))
    assert checks.trees_differ(_host(given.trees), trees) == 0
    assert np.asarray(trees[0]["leaf"]).shape == (K, 1 << DEPTH)
    assert np.asarray(trees[0]["feat"]).shape == (K, DEPTH,
                                                  1 << (DEPTH - 1))


@pytest.mark.parametrize("how", ["given", "learned"])
def test_a_label_outside_the_classes_is_refused(fitted, how):
    X, y, model, handle, trees = fitted
    bad = y.copy()
    bad[5] = K
    m = _model(num_class=K) if how == "given" else model
    with pytest.raises(Error):
        m.make_device_data(X, bad)
    with pytest.raises(Error):
        m.make_device_data(X, np.where(y == 0, -1.0, y).astype(np.float32))


# -- the host's edge ------------------------------------------------------------------

def test_margins_are_class_major_on_the_device_only(fitted):
    X, y, model, handle, trees = fitted
    assert model._train_preds.shape == (K, handle["n_padded"])
    margin = model.predict(X, output_margin=True)
    assert margin.shape == (N, K) and margin.flags["C_CONTIGUOUS"]
    want = rm.ensemble_margin(X, np.asarray(model.cuts), trees, 0.0)
    assert np.abs(margin - want).max() < 1e-5
    tm = model.train_margins()
    assert tm.shape == (N, K)
    np.testing.assert_allclose(tm, margin, rtol=0, atol=1e-5)
    # class c's trees are column c: shifted by one they are another model
    assert np.abs(margin - rm.ensemble_margin(
        X, np.asarray(model.cuts), trees, 0.0, shift=1)).max() > 0.1


def test_predict_and_predict_proba(fitted):
    X, y, model, handle, trees = fitted
    proba = model.predict_proba(X)
    assert proba.shape == (N, K)
    np.testing.assert_allclose(proba.sum(axis=1), 1.0, atol=1e-5)
    pred = model.predict(X)
    assert pred.shape == (N,) and (proba.argmax(axis=1) == pred).all()
    assert (pred == y).mean() > 0.5
    assert model.predict(X[:0]).shape == (0,)
    assert model.predict(X[:0], output_margin=True).shape == (0, K)


def test_softprob_is_softmaxs_training_and_answers_probabilities(fitted):
    X, y, model, handle, trees = fitted
    prob = _model(objective="multi:softprob")
    prob.fit_device(prob.make_device_data(X, y))
    assert checks.trees_differ(_host(prob.trees), trees) == 0
    got = prob.predict(X)
    assert got.shape == (N, K)
    assert np.array_equal(got, prob.predict_proba(X))
    np.testing.assert_allclose(got, model.predict_proba(X), atol=1e-6)
    assert prob.predict(X[:0]).shape == (0, K)


# -- a mesh ---------------------------------------------------------------------------

def test_four_devices_grow_one_devices_trees(fitted):
    """The same splits; the leaves to what float32 sums of a thousand
    rows a shard, added up, differ from one sum of four thousand (the
    softmax's gradients are no dyadic numbers, as a sigmoid's first are:
    a few 1e-4 of a leaf, 1e-3 of the smallest)."""
    X, y, model, handle, trees = fitted
    m4 = _model(mesh_devices=4)
    h4 = m4.make_device_data(X, y)
    m4.fit_device(h4)
    assert m4._train_preds.shape == (K, h4["n_padded"])
    assert m4.round_plan["mesh_devices"] == 4
    for t4, t1 in zip(_host(m4.trees), trees):
        np.testing.assert_array_equal(t4["feat"], t1["feat"])
        np.testing.assert_array_equal(t4["thr"], t1["thr"])
        np.testing.assert_allclose(t4["leaf"], t1["leaf"], rtol=5e-3,
                                   atol=1e-4)
    np.testing.assert_allclose(m4.train_margins(), model.train_margins(),
                               rtol=5e-3, atol=3e-4)


# -- the plan's record ------------------------------------------------------------------

def test_round_plan_says_how_many_trees_a_round_and_how_margins_lie(fitted):
    X, y, model, handle, trees = fitted
    plan = model.round_plan
    assert (plan["num_class"], plan["trees_per_round"],
            plan["margin_layout"]) == (K, K, "[num_class, n]")
    binary = HistGBT(n_trees=1, max_depth=2, n_bins=16, mesh=local_mesh(1))
    binary.fit(X, (y > 2).astype(np.float32))
    plan = binary.round_plan
    assert (plan["num_class"], plan["trees_per_round"],
            plan["margin_layout"]) == (1, 1, "[n]")
