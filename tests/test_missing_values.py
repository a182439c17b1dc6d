"""ISSUE 42: NaN as missing, end to end at toy size on the CPU.

(a) the unweighted missing summary reads its quantile points off a sort
of the keys alone: against the path it replaces (the permutation path,
still there for weights, given weights of one) and against the plain
reference's cuts; (b) a depth-3 fit on ``bosch_like`` against
``benchmark/reference_missing.py`` — root gain, direction, leaves,
held-out margins through ``predict``; (c) the staged round under
``_missing`` with feature blocks AND node blocks forced small,
byte-equal to the unblocked round; (d) the spans, record fields and the
plan's ``missing`` that ISSUE 42 adds.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import checks_missing, datagen_missing  # noqa: E402
from benchmark import reference_missing as ref  # noqa: E402
from dmlc_core_tpu.models import HistGBT  # noqa: E402
from dmlc_core_tpu.ops import histogram as H  # noqa: E402
from dmlc_core_tpu.ops.quantile import (apply_bins_missing,  # noqa: E402
                                        compute_cuts, local_summary,
                                        merge_summaries)
from dmlc_core_tpu.parallel.mesh import local_mesh  # noqa: E402
from dmlc_core_tpu.utils import profiler  # noqa: E402

from test_hist_feature_blocks import _budget  # noqa: E402
from test_hist_node_blocks import _cap, _sha  # noqa: E402

SEED = 2**31 + 42


# -- (a) the summary ---------------------------------------------------------

def _holes(n, F, share, seed=0, max_first=True):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, F)).astype(np.float32)
    X[rng.random((n, F)) < share] = np.nan
    X[0] = 9.0 if max_first else rng.standard_normal(F)
    return X


@pytest.mark.parametrize("n_summary, value_bins", [(2040, 255), (248, 31)])
@pytest.mark.parametrize("share", [0.0, 0.5, 0.81, 0.999])
def test_summary_against_the_path_it_replaces(share, n_summary, value_bins):
    """The permutation path gives NaN weight 0 AT the column's maximum:
    where the maximum itself comes first in row order those knots lie
    behind it and move nothing, and the two summaries are the same
    quantile function — equal to float32 rounding, the bins equal cell
    for cell."""
    X = jnp.asarray(_holes(4096, 12, share))
    new = local_summary(X, None, n_summary, True)
    old = local_summary(X, jnp.ones(X.shape[0], jnp.float32), n_summary, True)
    # the replaced path places its points by float32 probabilities
    # ((cw - 0.5) / total: a ten-thousandth of a step between two
    # values); the new one by exact integer positions
    scale = np.maximum(np.abs(np.asarray(old)), 1.0)
    assert np.max(np.abs(np.asarray(new) - np.asarray(old)) / scale) < 2e-4
    Xh = np.asarray(X)
    want = np.stack([ref.finite_summary(Xh[:, f], n_summary)
                     for f in range(Xh.shape[1])])
    assert np.max(np.abs(np.asarray(new) - want) / scale) < 2e-6
    cuts_new = merge_summaries(new[None], value_bins)
    cuts_old = merge_summaries(old[None], value_bins)
    np.testing.assert_allclose(cuts_new, cuts_old, rtol=2e-4, atol=2e-4)
    # the same bins but where a value lies within that rounding of a cut
    # (a summary of more points than the column has values puts cuts ON
    # values): fewer than one cell in a thousand, every one by one bin
    b_new = np.asarray(apply_bins_missing(X, cuts_new, value_bins + 1), int)
    b_old = np.asarray(apply_bins_missing(X, cuts_old, value_bins + 1), int)
    assert np.abs(b_new - b_old).max() <= 1
    assert np.count_nonzero(b_new != b_old) < 1e-3 * b_new.size
    assert np.array_equal(b_new == value_bins + 1, np.isnan(Xh))


def test_where_the_replaced_path_differed_the_new_one_is_the_rule():
    """With the maximum NOT first, the replaced path's zero-weight knots
    that precede it in row order sit at probability ``k / c`` and pull
    the last half step of the quantile function up to the maximum: it
    left the midpoint rule there, by up to half a step of the top value.
    The new summary is the rule itself (the reference's, to rounding)."""
    X = _holes(8192, 6, 0.999, seed=3, max_first=False)
    new = np.asarray(local_summary(jnp.asarray(X), None, 256, True))
    old = np.asarray(local_summary(jnp.asarray(X), jnp.ones(len(X)), 256,
                                   True))
    want = np.stack([ref.finite_summary(X[:, f], 256) for f in range(6)])
    assert np.max(np.abs(new - want)) < 1e-5
    assert np.max(np.abs(old - want)) > 1e-2
    # ... and only above the last value's own knot
    c = (~np.isnan(X)).sum(axis=0)
    q = np.linspace(0.0, 1.0, 256)
    for f in range(6):
        below = q <= (c[f] - 1.5) / c[f]
        assert np.max(np.abs(old[f, below] - want[f, below])) < 1e-5


@pytest.mark.parametrize("share", [0.0, 0.5, 0.81, 0.999])
def test_cuts_against_the_reference(share):
    X = _holes(6000, 8, share, seed=1, max_first=False)
    cuts = np.asarray(compute_cuts(X, 255, missing=True))
    assert cuts.shape == (8, 254)
    cfg = {"n_bins": 256, "n_summary": 2040}
    assert checks_missing.cuts_gap(X, cuts, range(8), cfg) < 1e-5
    # the control: the rule of the dense path (positions q * (c - 1))
    # on the values that are there is another rule, and shows
    dense = np.stack([np.quantile(np.quantile(
        X[~np.isnan(X[:, f]), f].astype(np.float64),
        np.linspace(0, 1, 2040)), np.linspace(0, 1, 256)[1:-1])
        for f in range(8)])
    if share == 0.999:
        assert checks_missing.cuts_gap(X, dense, range(8), cfg) > 1e-3


def test_one_value_ties_at_the_maximum_and_an_empty_column():
    n = 512
    X = np.full((n, 4), np.nan, np.float32)
    X[37, 0] = -2.5                          # one value
    X[:, 1] = np.where(np.arange(n) % 3 == 0, 7.0,
                       np.linspace(-1, 1, n))  # a third of it the maximum
    X[::5, 1] = np.nan
    X[:, 2] = np.linspace(0, 1, n)           # nothing missing
    s = np.asarray(local_summary(jnp.asarray(X), None, 64, True))
    assert np.all(s[0] == -2.5)
    assert np.all(np.isnan(s[3]))            # the sentinel row
    for f in (1, 2):
        np.testing.assert_allclose(s[f], ref.finite_summary(X[:, f], 64),
                                   rtol=1e-6, atol=1e-6)
    assert s[1, -1] == 7.0 and np.all(np.diff(s[1]) >= 0)
    # positions are exact integers: a column longer than float32 counts
    # (2**24) is read at the right rows (the arithmetic, not a sort)
    c, S = 2**30 + 12345, 2048
    j = np.arange(S, dtype=np.int64)
    a, b = c // (S - 1), c % (S - 1)
    k = j * a + (j * b) // (S - 1)
    assert np.array_equal(k, (j * c) // (S - 1)) and k.max() < 2**31


# -- (b) a fit against the reference ---------------------------------------------

@pytest.fixture(scope="module")
def bosch():
    X, y = datagen_missing.bosch_like(4096, 64, SEED)
    Xh, yh = datagen_missing.bosch_like(1024, 64, SEED, stream=1)
    return X, y, Xh, yh


CFG = {"max_depth": 3, "n_bins": 32, "learning_rate": 0.3, "reg_lambda": 1.0,
       "min_child_weight": 1.0, "base_score": 0.0, "n_summary": 248}


@pytest.fixture(scope="module")
def fitted(bosch):
    X, y, _, _ = bosch
    m = HistGBT(n_trees=3, mesh=local_mesh(1), objective="binary:logistic",
                **{k: CFG[k] for k in CFG if k != "n_summary"})
    handle = m.make_device_data(X, y)
    m.fit_device(handle)
    return m, handle


def test_the_table_is_bosch_shaped(bosch):
    X, y, Xh, _ = bosch
    assert 0.78 < np.isnan(X).mean() < 0.84
    assert np.isfinite(X).any(axis=0).all()
    line = datagen_missing.line_of(64, SEED)
    # a station is visited whole or not at all
    for lo, hi in zip(line.bounds[:-1], line.bounds[1:]):
        nan = np.isnan(X[:, lo:hi])
        assert np.all(nan.all(axis=1) | (~nan).all(axis=1))
    assert 0.002 < y.mean() < 0.012
    X2, y2 = datagen_missing.bosch_like(4096, 64, SEED)
    assert np.array_equal(X, X2, equal_nan=True) and np.array_equal(y, y2)
    assert not np.array_equal(X[:1024], Xh, equal_nan=True)


def test_a_fit_against_the_reference(bosch, fitted):
    X, y, Xh, yh = bosch
    m, handle = fitted
    assert m.round_plan["missing"] is True
    cuts = np.asarray(m.cuts)
    assert checks_missing.cuts_gap(X, cuts, range(0, 64, 7), CFG) < 1e-5
    bins_t = np.asarray(handle["bins_t"])[:, :len(y)]
    assert checks_missing.bin_numbers(X, bins_t, cuts, CFG) == {
        "bins_mismatches": 0, "missing_bin_mismatches": 0}
    trees = [{k: np.asarray(v) for k, v in t.items()} for t in m.trees]
    assert "dir" in trees[0]
    got = checks_missing.boost_tree_numbers(bins_t, y, trees, CFG)
    assert got["tree0.root_gain_gap"] < 1e-5
    assert got["tree0.reported_gain_gap"] < 1e-3   # float32 over G^2/H
    assert got["tree0.root_dir_differs"] == 0
    assert got["tree0.leaf_gap"] < 1e-5
    assert got["tree1.leaf_gap_by_rows"] < 1e-4
    # held-out rows through predict: NaN by each node's direction
    margin = ref.ensemble_margin(Xh, cuts, trees, CFG["base_score"])
    np.testing.assert_allclose(m.predict(Xh, output_margin=True), margin,
                               rtol=1e-4, atol=1e-5)
    # the root learned a direction a fixed one would not have: the rule
    # sends the rows WITHOUT station A to the failures' side
    t0 = trees[0]
    line = datagen_missing.line_of(64, SEED)
    assert line.bounds[line.station_a] <= t0["feat"][0, 0] \
        < line.bounds[line.station_a + 1]
    assert t0["dir"][0, 0] == 0


@pytest.mark.parametrize("control, leaves, floor", [
    ("force_left", "tree0.root_gain_gap", 0.1),
    ("bfloat16", "tree1.leaf_gap_by_rows", 1e-3),
    ("float8", "tree1.leaf_gap_by_rows", 1e-2)])
def test_each_control_leaves_a_limit(bosch, fitted, control, leaves, floor):
    X, y, _, _ = bosch
    m, handle = fitted
    bins_t = np.asarray(handle["bins_t"])[:, :len(y)]
    trees = [{k: np.asarray(v) for k, v in t.items()} for t in m.trees]
    wrong = checks_missing.control_trees(bins_t, y, trees, CFG, control)
    got = checks_missing.boost_tree_numbers(bins_t, y, wrong, CFG)
    assert got[leaves] > floor
    # NaN aliased into the top value bin: every hole is a mismatch
    aliased = ref.bin_rows(X[:256], np.asarray(m.cuts), alias_missing=True)
    nums = checks_missing.bin_numbers(X[:256], aliased.T, np.asarray(m.cuts),
                                      CFG)
    assert nums["missing_bin_mismatches"] == np.isnan(X[:256]).sum() > 0


# -- (c) blocks inside blocks under _missing ---------------------------------------

def test_blocked_missing_round_is_the_unblocked_round(bosch, monkeypatch):
    """Depth 6 under ``_missing``: the last level's 16 builds in node
    blocks of 4, each in feature blocks of 16 rows, against the round
    whose every build is one kernel call — every array of every tree
    the same bytes, ``dir`` among them."""
    X, y, _, _ = bosch
    kw = dict(mesh=local_mesh(1), n_trees=2, max_depth=6, n_bins=32,
              learning_rate=0.3, hist_method="pallas",
              objective="binary:logistic")

    def fit():
        m = HistGBT(**kw)
        m.fit_device(m.make_device_data(X, y))
        return m

    whole = fit()
    _cap(monkeypatch, 4)
    monkeypatch.setattr(H, "_SCOPED_VMEM", _budget(16))
    blocked = fit()
    assert whole.round_plan["missing"] and blocked.round_plan["missing"]
    assert whole.round_plan["hist_node_blocks"][-1] == [16]
    assert whole.round_plan["hist_feature_blocks"][-1] == [64]
    assert blocked.round_plan["hist_node_blocks"][-1] == [4] * 4
    assert blocked.round_plan["hist_feature_blocks"][-1] == [16] * 4
    assert "dir" in blocked.trees[0]
    assert _sha(blocked.trees) == _sha(whole.trees)
    assert np.array_equal(blocked.predict(X[:512]), whole.predict(X[:512]))


# -- (d) the marks ------------------------------------------------------------------

def test_the_record_says_missing_and_how_much(bosch):
    X, y, _, _ = bosch
    m = HistGBT(n_trees=1, max_depth=2, n_bins=16, mesh=local_mesh(1))
    before = len(profiler.op_log())
    m.make_device_data(X, y)
    m._pending_warmup.join()
    (rec,) = [r for r in profiler.op_log()[before:]
              if r["name"] == "dmlc.ingest"]
    assert rec["counts"]["missing"] == 1
    assert rec["counts"]["missing_share"] == pytest.approx(
        np.isnan(X).mean())
    # a first ingest: the matrix the cut sort reads is scanned where it
    # was put, inside the cuts span, and the host reads nothing of it
    assert rec["counts"]["nan_scan"] == "device"
    n, seconds, _longest, nbytes = \
        rec["children"]["dmlc.ingest.cuts.nan_scan"]
    assert (n, nbytes) == (1, X.nbytes) and seconds > 0
    assert seconds <= rec["children"]["dmlc.ingest.cuts"][1]
    assert "dmlc.ingest.host_prep.nan_scan" not in rec["children"]
    # one chip, one slab: ONE put, inside the cuts span, and the slab is
    # binned as it lies
    assert rec["children"]["dmlc.ingest.put"][0] == 1
    assert rec["children"]["dmlc.ingest.put"][3] == X.nbytes
    assert "dmlc.ingest.put_wait" not in rec["children"]
    dense = HistGBT(n_trees=1, max_depth=2, n_bins=16, mesh=local_mesh(1))
    dense.make_device_data(np.nan_to_num(X), y)
    dense._pending_warmup.join()
    rec = [r for r in profiler.op_log() if r["name"] == "dmlc.ingest"][-1]
    assert (rec["counts"]["missing"], rec["counts"]["missing_share"]) == \
        (0, 0.0)
    assert dense._round_plan(64).missing is False


def test_the_summary_has_its_own_device_scope():
    x = jax.ShapeDtypeStruct((64, 4), jnp.float32)
    text = local_summary.lower(x, None, 16, True).as_text(debug_info=True)
    assert "dmlc.cuts/dmlc.cuts.finite" in text.replace("jit(", "").replace(
        ")", "") or "dmlc.cuts.finite" in text
    assert "sort" in text and "iota" not in text.split("sort")[0][-400:]
    dense = local_summary.lower(x, None, 16, False).as_text(debug_info=True)
    assert "dmlc.cuts.finite" not in dense


def test_a_matrix_past_the_put_cliff_goes_in_pieces(monkeypatch):
    """2**32 bytes in one transfer crawl (22 s for 4.58 GB on the chip):
    such a matrix is put in row pieces and written into place, the same
    array, and a second ingest compiles nothing more."""
    from dmlc_core_tpu.base import compile_cache
    from dmlc_core_tpu.models import histgbt as G

    X = _holes(1001, 7, 0.5, seed=5, max_first=False)
    y = (np.nan_to_num(X[:, 0]) > 0).astype(np.float32)
    monkeypatch.setattr(G, "_PUT_CLIFF_BYTES", 1000)
    monkeypatch.setattr(G, "_PUT_PIECE_BYTES", 9000)

    def ingest():
        m = HistGBT(n_trees=1, max_depth=2, n_bins=16, mesh=local_mesh(1))
        before = len(profiler.op_log())
        h = m.make_device_data(X, y)
        m._pending_warmup.join()
        (rec,) = [r for r in profiler.op_log()[before:]
                  if r["name"] == "dmlc.ingest"]
        return m, h, rec

    m, h, rec = ingest()
    n_put, _s, _l, nbytes = rec["children"]["dmlc.ingest.put"]
    assert n_put == 4 and nbytes == X.nbytes          # 4 pieces of <= 9000 B
    monkeypatch.undo()
    whole, h1, rec1 = ingest()
    assert rec1["children"]["dmlc.ingest.put"][0] == 1
    assert np.array_equal(np.asarray(m.cuts), np.asarray(whole.cuts))
    assert np.array_equal(np.asarray(h["bins_t"]), np.asarray(h1["bins_t"]))
    monkeypatch.setattr(G, "_PUT_CLIFF_BYTES", 1000)
    monkeypatch.setattr(G, "_PUT_PIECE_BYTES", 9000)
    st = compile_cache.stats()
    ingest()
    after = compile_cache.stats()
    assert (after["hits"], after["misses"]) == (st["hits"], st["misses"])
