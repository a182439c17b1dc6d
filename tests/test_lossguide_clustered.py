"""ISSUE 57: a leaf-wise tree's rows CLUSTERED by leaf, and histogram
builds that skip the tiles with none of their node.

* ``_hist_pallas_skip``, the call with a per-tile liveness vector
  (scalar prefetch: a dead tile is neither fetched nor computed),
  builds, bit for bit, what
  the plain call builds over the same rows; the plain call's program is
  untouched (``test_hist_class_blocks.py`` holds its jaxpr by sha256);
* a fit with the clustered path forced — the tile and the row gate
  lowered, the re-cluster point both below and at ``max_leaves - 2`` —
  grows the node lists of today's path and hands the per-row delta back
  in input order; two fits are byte-identical;
* the engagement rule (``HistGBT._round_plan`` +
  ``ops.recluster_points``): a class axis, a ``layout``,
  ``DMLC_HIST_BLOCKS``, a non-Pallas build, few rows, few leaves and a
  wide matrix each keep today's one scan;
* after a fit ``round_plan["hist_rows_per_build"]`` is what the last
  tree's kernels computed, between the rows the builds need and all of
  them.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import reference_lossguide as rl  # noqa: E402
from dmlc_core_tpu.models import HistGBT  # noqa: E402
from dmlc_core_tpu.models import histgbt as G  # noqa: E402
from dmlc_core_tpu.ops import binlayout as bl  # noqa: E402
from dmlc_core_tpu.ops import histogram as H  # noqa: E402
from dmlc_core_tpu.parallel.mesh import local_mesh  # noqa: E402

TILE = 128


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 128 rows and no row gate: a few thousand rows are many
    tiles, and a tree of a few leaves pays for its sort."""
    monkeypatch.setattr(H, "_TILE_ROWS", TILE)
    monkeypatch.setattr(H, "RECLUSTER_MIN_ROWS", 0)
    monkeypatch.setattr(H, "_RECLUSTER_COST_BUILDS", 0)
    monkeypatch.setattr(G, "_ROUND_FN_CACHE", {})
    monkeypatch.setattr(G, "_AOT_EXEC_CACHE", {})


# -- the kernel -----------------------------------------------------------------

def _rows(F, n, n_bins, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, n_bins, (F, n)).astype(np.uint8)),
            jnp.asarray(rng.normal(size=n).astype(np.float32)),
            jnp.asarray(rng.random(n).astype(np.float32)))


def _skip_build(bins_t, node, g, h, n_nodes, n_bins):
    F = bins_t.shape[0]
    bins_x, node_x, g_x, h_x = H.tile_aligned(bins_t, jnp.asarray(node), g, h)
    live = H.tile_liveness(node_x)
    return np.asarray(H.build_histogram(
        bins_x, node_x, g_x, h_x, n_nodes, n_bins, "pallas",
        transposed=True, tile_live=live, n_features=F)), np.asarray(live)


@pytest.mark.parametrize("F,n,n_bins", [
    (13, 7 * TILE + 37, 256),        # pad features, rows not whole tiles
    (8, 6 * TILE, 64),               # nothing to pad
    (28, 5 * TILE + 1, 256),         # the leaf-wise cell's width
])
def test_a_liveness_vector_changes_no_bit(small_tiles, F, n, n_bins):
    bins_t, g, h = _rows(F, n, n_bins)
    node = np.full(n, -1, np.int32)
    node[2 * TILE + 5:4 * TILE - 9] = 0          # tiles 2 and 3
    node[n - 1] = 0                              # and the last one
    want = np.asarray(H.build_histogram(
        bins_t, jnp.asarray(node), g, h, 1, n_bins, "pallas",
        transposed=True))
    got, live = _skip_build(bins_t, node, g, h, 1, n_bins)
    grid = -(-n // TILE)
    assert live.tolist() == [int(i in (2, 3, grid - 1)) for i in range(grid)]
    assert got.shape == want.shape == (2, 1, F, n_bins)
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    # no live tile before the first rows, none after the last: the dead
    # steps at either end name a live tile's blocks and add nothing
    assert np.abs(got).sum() > 0


def test_an_all_dead_build_is_zeros(small_tiles):
    bins_t, g, h = _rows(11, 4 * TILE + 9, 256)
    got, live = _skip_build(bins_t, np.full(4 * TILE + 9, -1, np.int32),
                            g, h, 1, 256)
    assert not live.any() and not got.any()


def test_feature_blocks_take_the_same_vector(small_tiles, monkeypatch):
    """A matrix wider than one feature block: every block's call skips
    by the one vector, the last block takes the pad rows with it."""
    from test_hist_feature_blocks import _budget

    monkeypatch.setattr(H, "_SCOPED_VMEM", _budget(16))
    F, n = 44, 5 * TILE + 17
    assert H.hist_feature_blocks(256, F, 1) == (16, 16, 12)
    bins_t, g, h = _rows(F, n, 256)
    node = np.where(np.arange(n) // TILE == 1, 0, -1).astype(np.int32)
    want = np.asarray(H.build_histogram(
        bins_t, jnp.asarray(node), g, h, 1, 256, "pallas", transposed=True))
    got, _ = _skip_build(bins_t, node, g, h, 1, 256)
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))


def test_recluster_rows_is_a_stable_reordering():
    rng = np.random.default_rng(3)
    F, n = 13, 1000
    Fp = 16
    bins = np.zeros((Fp, n), np.uint8)
    bins[:F] = rng.integers(0, 256, (F, n))
    key = rng.integers(0, 7, n).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    order = np.arange(n, dtype=np.int32)
    k2, b2, g2, o2 = (np.asarray(a) for a in H.recluster_rows(
        jnp.asarray(key), jnp.asarray(bins), F, jnp.asarray(g),
        jnp.asarray(order)))
    perm = np.argsort(key, kind="stable")
    assert np.array_equal(o2, perm) and np.array_equal(k2, key[perm])
    assert np.array_equal(b2, bins[:, perm])
    assert np.array_equal(g2.view(np.uint32), g[perm].view(np.uint32))


# -- the fit ----------------------------------------------------------------------

def _xy(n=2003, F=7, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    X[:, 2] = rng.integers(0, 3, n)
    y = ((X[:, 0] + 0.5 * X[:, 2] - X[:, 1] * X[:, 3]) > 0
         ).astype(np.float32)
    return X, y


KW = dict(n_trees=3, max_depth=0, n_bins=32, objective="binary:logistic",
          learning_rate=0.3, grow_policy="lossguide", hist_method="pallas")


def _fit(X, y, leaves, mesh=1, **kw):
    model = HistGBT(mesh=local_mesh(mesh), max_leaves=leaves, **{**KW, **kw})
    model.fit(X, y)
    return model


@pytest.mark.parametrize("leaves,point", [
    (12, 2),             # the point below max_leaves - 2
    (3, 1),              # at max_leaves - 2: ONE expansion after it
    (26, 3),
])
def test_a_clustered_fit_grows_todays_node_lists(monkeypatch, leaves, point):
    X, y = _xy()
    base = _fit(X, y, leaves)
    assert base.round_plan["recluster_at"] == []
    assert base.round_plan["hist_rows_per_build"] == len(y)
    base_margin = base.predict(X, output_margin=True)
    base_train = np.asarray(base._train_preds)[:len(y)]
    for name, value in (("_TILE_ROWS", TILE), ("RECLUSTER_MIN_ROWS", 0),
                        ("_RECLUSTER_COST_BUILDS", 0)):
        monkeypatch.setattr(H, name, value)
    monkeypatch.setattr(G, "_ROUND_FN_CACHE", {})
    monkeypatch.setattr(G, "_AOT_EXEC_CACHE", {})
    model = _fit(X, y, leaves)
    assert model.round_plan["recluster_at"] == [point]
    assert len(model.trees) == len(base.trees) == KW["n_trees"]
    for a, b in zip(base.trees, model.trees):
        assert set(a) == set(b)              # the tile count is not a tree's
        for k in ("feat", "thr", "left", "right"):
            assert np.array_equal(a[k], b[k]), k
        for k in ("gain", "value"):
            np.testing.assert_allclose(b[k], a[k], rtol=2e-6, atol=1e-7)
    # the per-row delta came back in input order: the margins the next
    # round starts from, and the stored ones, are each row's own
    np.testing.assert_allclose(model.predict(X, output_margin=True),
                               base_margin, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(model._train_preds)[:len(y)],
                               base_train, rtol=1e-5, atol=1e-6)
    # two fits of the clustered program are byte-identical
    again = _fit(X, y, leaves)
    for a, b in zip(model.trees, again.trees):
        for k in a:
            assert np.array_equal(a[k].view(np.uint8), b[k].view(np.uint8))
    assert again.round_plan == model.round_plan


def _built_rows(tree, bins):
    """Rows the builds of a node list NEED as the program builds it: the
    root's and every split node's LEFT child's (``bins`` ``[n, F]``)."""
    n = len(bins)
    node = np.zeros(n, np.int64)
    rows = np.zeros(len(tree["left"]), np.int64)
    rows[0] = n
    moving = np.ones(n, bool)
    while moving.any():
        at = node[moving]
        split = tree["left"][at] >= 0
        right = bins[moving, tree["feat"][at]] > tree["thr"][at]
        node[moving] = np.where(split, np.where(right, tree["right"][at],
                                                tree["left"][at]), at)
        moving[moving] = split
        rows += np.bincount(node[moving], minlength=len(rows))
    return int(n + sum(rows[l] for l in tree["left"] if l >= 0))


def test_clustered_builds_compute_between_needed_and_all(small_tiles):
    from benchmark import reference as ref

    X, y = _xy(n=4001)
    n = len(y)
    model = _fit(X, y, 12, n_trees=2)
    assert model.round_plan["recluster_at"] == [2]
    per_build = model.round_plan["hist_rows_per_build"]
    tree = model.trees[-1]
    builds = int((tree["left"] >= 0).sum()) + 1
    assert builds == len(rl.leaves_of(tree)) == 12
    needed = _built_rows(tree, ref.bin_rows(X, np.asarray(model.cuts)))
    # whole tiles of the needed rows at the least (a tile is computed
    # for one row of the node), every tile of every build at the most
    assert needed // builds <= per_build < -(-n // TILE) * TILE
    # twelve leaves' clusters are not all one cluster: skipping showed
    assert per_build < 0.7 * n


def test_a_mesh_clusters_each_shard(small_tiles):
    X, y = _xy(n=4096)
    one = _fit(X, y, 12)
    four = _fit(X, y, 12, mesh=4)
    assert four.round_plan["recluster_at"] == [2]
    assert four.round_plan["mesh_devices"] == 4
    for a, b in zip(one.trees, four.trees):
        for k in ("feat", "thr", "left", "right"):
            assert np.array_equal(a[k], b[k]), k
        np.testing.assert_allclose(b["value"], a["value"], rtol=1e-5,
                                   atol=1e-7)
    assert 0 < four.round_plan["hist_rows_per_build"] <= 1024


# -- the engagement rule ------------------------------------------------------------

def test_the_rule_of_the_points():
    assert H.recluster_points(255, 24_000_000, 28) == (8,)
    assert H.recluster_points(255, 10_000_000, 28) == (8,)
    assert H.recluster_points(255, H.RECLUSTER_MIN_ROWS - 1, 28) == ()
    assert H.recluster_points(1023, 1 << 22, 64) == (16,)
    # a sort operand every four features: not past 16 of them
    assert H.recluster_points(1023, 1 << 22, 65) == ()
    # too few expansions after the point to pay for the sort
    assert H.recluster_points(31, 24_000_000, 28) == ()
    leaves = next(v for v in range(2, 4096)
                  if H.recluster_points(v, 1 << 24, 28))
    s, = H.recluster_points(leaves, 1 << 24, 28)
    assert leaves - 1 - s == H._RECLUSTER_COST_BUILDS + 1


@pytest.mark.parametrize("case", ["engaged", "class_axis", "layout",
                                  "hist_blocks", "segment", "few_rows",
                                  "few_leaves", "wide", "depthwise"])
def test_what_engages_the_clustered_path(monkeypatch, case):
    """Every side of the rule, from the plan alone: nothing is traced."""
    monkeypatch.setattr(H, "RECLUSTER_MIN_ROWS", 1 << 12)
    monkeypatch.setattr(H, "_RECLUSTER_COST_BUILDS", 4)
    kw = dict(n_trees=2, max_depth=0, max_leaves=26, n_bins=32,
              grow_policy="lossguide", hist_method="pallas",
              objective="binary:logistic")
    rows, mesh, layout, features = 1 << 13, 1, None, 7
    if case == "class_axis":
        kw.update(objective="multi:softmax", num_class=3)
    elif case == "layout":
        bins_t = np.random.default_rng(0).integers(
            0, 32, size=(7, 256)).astype(np.uint8)
        bins_t[:4] %= 5
        layout = bl.compute_layout(bl.bin_counts(bins_t, 32), 7, 32)
        assert layout is not None
    elif case == "hist_blocks":
        monkeypatch.setenv("DMLC_HIST_BLOCKS", "4")
    elif case == "segment":
        kw.update(hist_method="segment")
    elif case == "few_rows":
        rows = (1 << 12) - 1
    elif case == "few_leaves":
        kw.update(max_leaves=6)          # 6 - 1 - 1 <= 4
    elif case == "wide":
        features = H._RECLUSTER_MAX_FEATURES + 1
    elif case == "depthwise":
        kw.update(grow_policy="depthwise", max_depth=4, max_leaves=0)
    model = HistGBT(mesh=local_mesh(mesh), **kw)
    model._bin_layout = layout
    plan = model._round_plan(features, rows)
    want = (3,) if case == "engaged" else ()
    assert plan.recluster_at == want
    if case == "depthwise":
        assert "recluster_at" not in model.round_plan
    else:
        assert model.round_plan["recluster_at"] == list(want)
        # before a fit: the bound, every build over all the rows
        assert model.round_plan["hist_rows_per_build"] == rows


def test_a_depthwise_plan_is_what_it_was():
    """The depth-wise plan's record gains no key and its program no
    operation: ``recluster_at`` lives on the plan with its default."""
    model = HistGBT(mesh=local_mesh(1), n_trees=2, max_depth=4, n_bins=32)
    plan = model._round_plan(7, 1 << 22)
    assert plan.recluster_at == ()
    assert "recluster_at" not in model.round_plan
    assert "hist_rows_per_build" not in model.round_plan
