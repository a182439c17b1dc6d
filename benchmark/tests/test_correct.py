"""Each comparison that decides ``correct`` passes on the program at toy
size and FAILS on its control: the reference put in the program's place
and computed one precision lower — bfloat16 for the float32 sums, float8
for the bfloat16 gradients.  The limits are the shipped mixes' own; the
readings at the cells' own size are ``control_on_chip.py``'s (PERF.md)."""

import json
import os

import numpy as np
import pytest

import util
from benchmark import checks, datagen, reference as ref

HERE = os.path.dirname(os.path.abspath(__file__))
CFG = dict(util.TINY_CONFIG, rows=20000, n_bins=64, n_summary=512,
           max_depth=4)


def shipped_limit(mix, name):
    with open(os.path.join(HERE, "..", "traffic", mix + ".json")) as f:
        lim = json.load(f)["limits"][name]
    return lim["limit"] if isinstance(lim, dict) else lim


@pytest.fixture(scope="module")
def fitted():
    """The program, fitted on the CPU at toy size."""
    from dmlc_core_tpu.models import HistGBT

    X, y = datagen.higgs_like(CFG["rows"], CFG["features"], 5)
    model = HistGBT(n_trees=8, max_depth=CFG["max_depth"],
                    n_bins=CFG["n_bins"])
    handle = model.make_device_data(X, y)
    model.fit_device(handle)
    trees = [{k: np.asarray(v) for k, v in t.items()} for t in model.trees]
    bins_t = np.asarray(handle["bins_t"])[:, :len(y)]
    return X, y, model, trees, bins_t, np.asarray(model.cuts)


def test_bf16_rounding_helper():
    x = np.array([1.0, 1.00390625, 3.14159, -2.71828, 0.0], np.float32)
    got = ref.to_bf16(x)
    assert got[0] == 1.0 and got[4] == 0.0
    assert got[1] == 1.0                      # tie rounds to even
    assert abs(got[2] - 3.14159) < 2 ** -7 and got[2] != np.float64(x[2])
    f8 = ref.to_fp8(np.array([0.3, -0.7312, 0.0625, 0.001, 0.0]))
    assert f8.tolist() == [0.3125, -0.75, 0.0625, 0.001953125, 0.0]


BOOST_NUMBERS = ["tree0.root_gain_gap", "tree0.reported_gain_gap",
                 "tree0.leaf_gap", "tree1.leaf_gap"]


def test_boost_numbers_pass_on_the_program(fitted):
    X, y, model, trees, bins_t, cuts = fitted
    got = checks.boost_tree_numbers(bins_t, y, trees, CFG)
    for name in BOOST_NUMBERS:
        assert got[name] <= shipped_limit("boost", name), (name, got)


def test_boost_numbers_fail_on_the_bf16_control(fitted):
    X, y, model, trees, bins_t, cuts = fitted
    control = checks.control_trees(bins_t, y, trees, CFG)
    got = checks.boost_tree_numbers(bins_t, y, control, CFG)
    failed = [n for n in BOOST_NUMBERS if got[n] > shipped_limit("boost", n)]
    # the lower precision has to fail one of the cell's numbers, not each:
    # a bfloat16 accumulator fails tree 0's leaves and reported gain at any
    # size (tree 1's limit is the wider one, set against float8 gradients)
    assert "tree0.leaf_gap" in failed, got
    assert "tree0.reported_gain_gap" in failed, got
    assert got["tree0.leaf_gap"] > 100 * shipped_limit("boost",
                                                       "tree0.leaf_gap")


def test_float8_gradients_pass_tree0_and_fail_tree1(fitted):
    """Why tree 1 is compared at all: tree 0's gradients are +-0.5 and
    0.25, exact in every format, so the control one step below the
    bfloat16 the configurations state for the gradients — float8, summed
    exactly — moves nothing there and fails only tree 1."""
    X, y, model, trees, bins_t, cuts = fitted
    control = checks.control_trees(bins_t, y, trees, CFG, "float8")
    got = checks.boost_tree_numbers(bins_t, y, control, CFG)
    assert got["tree0.leaf_gap"] == 0.0
    assert got["tree1.leaf_gap"] > 2 * shipped_limit("boost",
                                                     "tree1.leaf_gap")


def test_trees_differ_counts_arrays(fitted):
    trees = fitted[3]
    assert checks.trees_differ(trees, trees) == 0
    other = [dict(t) for t in trees]
    other[3] = dict(other[3], leaf=other[3]["leaf"] + np.float32(1e-7))
    assert checks.trees_differ(trees, other) == 1
    assert checks.trees_differ(trees, trees[:-1]) > 0


def test_cuts_pass_on_the_program_and_fail_on_the_control(fitted):
    X, y, model, trees, bins_t, cuts = fitted
    limit = shipped_limit("ingest", "cuts_gap")
    feats = [0, 3, 7]
    assert checks.cuts_gap(X, cuts, feats, CFG) <= limit
    control = np.stack([
        ref.quantile_cuts(X[:, f], CFG["n_bins"], CFG["n_summary"],
                          precision="bfloat16")
        for f in range(X.shape[1])])
    assert checks.cuts_gap(X, control, feats, CFG) > 3 * limit


def test_bins_pass_on_the_program_and_fail_on_the_control(fitted):
    X, y, model, trees, bins_t, cuts = fitted
    assert checks.bins_mismatches(X[:4096], bins_t[:, :4096], cuts) == 0
    control = ref.bin_rows(X[:4096], cuts, precision="bfloat16").T
    assert checks.bins_mismatches(X[:4096], control, cuts) > 100


def test_score_passes_on_the_program_and_fails_on_the_control(fitted):
    X, y, model, trees, bins_t, cuts = fitted
    limit = shipped_limit("score", "score_gap")
    Xh, _ = datagen.higgs_like(2048, CFG["features"], 5, stream=1)
    slabs = [Xh[:1024], Xh[1024:]]
    got = [model.predict(s) for s in slabs]
    assert checks.score_gap(slabs, got, cuts, trees, CFG) <= limit
    control = [ref.sigmoid(ref.ensemble_margin(s, cuts, trees, 0.0,
                                               precision="bfloat16"))
               for s in slabs]
    assert checks.score_gap(slabs, control, cuts, trees, CFG) > 30 * limit


def test_descents_agree(fitted):
    """Routing on raw values against the cuts is routing on bins."""
    X, y, model, trees, bins_t, cuts = fitted
    for t in trees[:3]:
        a = ref.descend_binned(bins_t[:, :2000], t["feat"], t["thr"])
        b = ref.descend_raw(X[:2000].astype(np.float64),
                            cuts.astype(np.float64), t["feat"], t["thr"])
        assert np.array_equal(a, b)


def test_auc_and_logloss():
    y = np.array([0, 0, 1, 1], np.float32)
    assert ref.auc(np.array([0.1, 0.4, 0.35, 0.8]), y) == pytest.approx(0.75)
    assert ref.auc(np.zeros(4), y) == pytest.approx(0.5)
    assert ref.logloss(np.zeros(4), y) == pytest.approx(np.log(2))
