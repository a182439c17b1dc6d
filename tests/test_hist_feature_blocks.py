"""Histograms in feature blocks (ISSUE 35).

A matrix wider than the Pallas kernel's VMEM budgets admit is built block
by block: the same kernel once per contiguous slab of feature rows, the
histograms joined on the feature axis.

* a blocked build equals the unblocked build byte for byte, with real
  (inexact) gradients: a feature's sums are made of the same operations
  in the same order either way;
* ``_pallas_ok`` — the function that gives the block — answers with the
  whole matrix exactly where the gate it was (transcribed below) said yes
  and the pipeline's double-buffered bins block fits too, and there the
  build traces ``_hist_pallas`` and nothing else: the parent's program;
* ``HistGBT.round_plan`` records the blocks of every build, and the
  traced round program holds exactly those kernel calls, with the
  environment unreadable once the plan is resolved;
* a wide staged fit is held to ``benchmark/reference.py`` by the limits
  the wide cell's mix states.

The blocks of the tests come from a test-sized budget (``_SCOPED_VMEM``
shrunk): blocks follow the budgets, nothing else selects them.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from dmlc_core_tpu.models import HistGBT  # noqa: E402
from dmlc_core_tpu.models import histgbt as hg  # noqa: E402
from dmlc_core_tpu.ops import binlayout as bl  # noqa: E402
from dmlc_core_tpu.ops import histogram as H  # noqa: E402
from dmlc_core_tpu.parallel.mesh import local_mesh  # noqa: E402

from test_round_plan import _NoLevers  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B = 64


def _budget(block: int) -> int:
    """The scoped-VMEM figure under which the double-buffered bins block
    admits ``block`` feature rows at the default tile."""
    return H._TILE_ROWS * (2 * block + H._SCOPED_ROW_RESERVE)


def _rows(F, n_nodes, n=700, seed=0):
    rng = np.random.default_rng(seed + 131 * F + n_nodes)
    bins_t = rng.integers(0, B, size=(F, n)).astype(np.uint8)
    node = rng.integers(0, n_nodes, size=n).astype(np.int32)
    node[::7] = -1                          # padded / pruned rows
    g = rng.normal(size=n).astype(np.float32)
    h = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    return bins_t, node, g, h


# -- blocked == unblocked, bit for bit ---------------------------------

@pytest.mark.parametrize("transposed", [True, False],
                         ids=["feature_major", "row_major"])
@pytest.mark.parametrize("n_nodes", [1, 4, 16])
@pytest.mark.parametrize("F, block", [(44, 16), (61, 24), (64, 16)])
def test_blocked_build_is_the_unblocked_build(F, block, n_nodes, transposed,
                                              monkeypatch):
    bins_t, node, g, h = _rows(F, n_nodes)
    args = [jnp.asarray(bins_t if transposed else bins_t.T),
            jnp.asarray(node), jnp.asarray(g), jnp.asarray(h)]
    assert H.hist_feature_blocks(B, F, n_nodes) == (F,)
    whole = np.asarray(H.build_histogram(*args, n_nodes, B, "pallas",
                                         transposed=transposed))
    monkeypatch.setattr(H, "_SCOPED_VMEM", _budget(block))
    blocks = H.hist_feature_blocks(B, F, n_nodes)
    # F = 44, 61: the last block is what is left, 61's no multiple of 8
    assert blocks == (block,) * (F // block) + ((F % block,) if F % block
                                                else ())
    assert len(blocks) >= 3
    got = np.asarray(H.build_histogram(*args, n_nodes, B, "pallas",
                                       transposed=transposed))
    assert got.shape == (2, n_nodes, F, B)
    assert got.tobytes() == whole.tobytes()
    assert got.any()
    # ... and, through the staged level's entry point, the same kernels
    if transposed and n_nodes > 1:
        feat = jnp.zeros(node.size, jnp.int32)
        left, _ = H.descend_histogram(args[0], args[1] // 2, feat, feat,
                                      args[2], args[3], n_nodes, B, "pallas")
        assert left.shape == (2, n_nodes, F, B)


def _pallas_calls(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        total += eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _pallas_calls(sub)
    return total


def test_a_blocked_build_is_one_kernel_call_a_block(monkeypatch):
    monkeypatch.setattr(H, "_SCOPED_VMEM", _budget(16))
    bins_t, node, g, h = _rows(44, 4)

    def build(*a):
        return H.build_histogram(*a, 4, B, "pallas", transposed=True)

    jaxpr = jax.make_jaxpr(build)(bins_t, node, g, h)
    assert _pallas_calls(jaxpr.jaxpr) == 3
    text = jax.jit(build).lower(bins_t, node, g, h).as_text(debug_info=True)
    assert "dmlc.hist.fblock" in text


# -- the block function against the gate it was -------------------------

def _gate_before_issue_35(n_bins, n_features, n_nodes=1, bins_itemsize=1,
                          tile_rows=0):
    """``_pallas_ok`` as PR 34 left it, word for word."""
    lo = H._lo_factor(n_nodes, n_bins)
    hi = -(-n_bins // lo)
    fp = -(-n_features // 8) * 8
    nh = n_nodes * hi
    acc = fp * 2 * nh * max(lo, 128) * 4
    T = tile_rows or H._TILE_ROWS
    tile_stack = T * (fp * bins_itemsize + 120 + 6 * nh + 2 * lo)
    return acc <= 24 << 20 and tile_stack <= 15 << 20


def _double_buffer_fits(n_features, bins_itemsize=1, tile_rows=0):
    T = tile_rows or H._TILE_ROWS
    fp = -(-n_features // 8) * 8
    return T * (2 * fp * bins_itemsize + H._SCOPED_ROW_RESERVE) \
        <= H._SCOPED_VMEM


@pytest.mark.parametrize("tile_rows", [0, 4096, 65536])
@pytest.mark.parametrize("itemsize", [1, 4])
@pytest.mark.parametrize("n_bins", [32, 64, 128, 200, 256, 512])
def test_block_is_the_whole_matrix_where_the_gate_said_yes(n_bins, itemsize,
                                                           tile_rows):
    for n_nodes in (1, 2, 4, 8, 16, 32, 64, 256):
        for F in list(range(1, 70)) + list(range(70, 3200, 37)):
            block = H._pallas_ok(n_bins, F, n_nodes, itemsize, tile_rows)
            was = _gate_before_issue_35(n_bins, F, n_nodes, itemsize,
                                        tile_rows)
            fits = _double_buffer_fits(F, itemsize, tile_rows)
            assert (block == F) == (was and fits), (F, n_nodes, block)
            # a block is whole, or a multiple of 8 that both pass
            if block and block != F:
                assert block % 8 == 0 and block < F
                assert _gate_before_issue_35(n_bins, block, n_nodes,
                                             itemsize, tile_rows)
                assert _double_buffer_fits(block, itemsize, tile_rows)
                bigger = block + 8
                assert not (_gate_before_issue_35(n_bins, bigger, n_nodes,
                                                  itemsize, tile_rows)
                            and _double_buffer_fits(bigger, itemsize,
                                                    tile_rows))
            blocks = H.hist_feature_blocks(n_bins, F, n_nodes, itemsize) \
                if not tile_rows else None
            if blocks is not None:
                assert sum(blocks) == (F if block else 0)
                assert all(b == block for b in blocks[:-1])


def test_the_shipped_shapes():
    # HIGGS: one block of 28 at every build of a depth-6 tree; Epsilon:
    # five blocks of 392 and one of 40, at every build
    for n_build in (1, 1, 2, 4, 8, 16):
        assert H.hist_feature_blocks(256, 28, n_build) == (28,)
        assert H.hist_feature_blocks(256, 2000, n_build) == (392,) * 5 + (40,)
        assert H.resolve_hist_method("pallas", 256, 2000, n_build) == "pallas"
    assert H.hist_feature_blocks(256, 392, 16) == (392,)
    # past depth 7 the kernel's one-hots are too tall for any feature
    # block of ONE call; the build runs in node blocks (ISSUE 37,
    # test_hist_node_blocks.py), each in the feature blocks of ITS nodes
    assert H.hist_feature_blocks(256, 28, 64) == ()
    assert H._pallas_ok(256, 28, 64) == 0
    assert H.hist_node_blocks(256, 28, 64) == (32, 32)


@pytest.mark.parametrize("F", [5, 28, 32, 392])
@pytest.mark.parametrize("n_nodes", [1, 16])
def test_at_one_block_the_build_traces_hist_pallas_alone(F, n_nodes):
    """Every shape the whole-matrix budgets admit: ``build_histogram``
    traces to the jaxpr of a plain ``_hist_pallas`` call (whose body
    this PR does not touch) — the parent's program, equation for
    equation."""
    n = 2 * 256
    shapes = (jnp.zeros((F, n), jnp.uint8), jnp.zeros(n, jnp.int32),
              jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.float32))
    got = jax.make_jaxpr(lambda *a: H.build_histogram(
        *a, n_nodes, 256, "pallas", transposed=True))(*shapes)
    want = jax.make_jaxpr(lambda *a: H._hist_pallas(
        *a, n_nodes, 256, transposed=True))(*shapes)
    assert str(got) == str(want)
    assert "fblock" not in str(got)


def test_a_packed_layout_is_never_cut(monkeypatch):
    """Nibble-packed rows lead the block: the matrix goes in whole or
    not at all."""
    rng = np.random.default_rng(0)
    bins_t = rng.integers(0, 32, size=(30, 256)).astype(np.uint8)
    bins_t[:20] %= 5
    lay = bl.compute_layout(bl.bin_counts(bins_t, 32), 30, 32, pack=True)
    assert lay.pairs
    assert H.resolve_hist_method("pallas", lay.sync_bins, lay.phys_rows, 1,
                                 whole=True) == "pallas"
    monkeypatch.setattr(H, "_SCOPED_VMEM", _budget(8))
    assert H._pallas_ok(lay.sync_bins, lay.phys_rows) == 8 < lay.phys_rows
    from dmlc_core_tpu.base.logging import Error
    with pytest.raises(Error, match="cannot be built in feature blocks"):
        H.resolve_hist_method("pallas", lay.sync_bins, lay.phys_rows, 1,
                              whole=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert H.resolve_hist_method("auto", lay.sync_bins, lay.phys_rows, 1,
                                 whole=True) == "segment"
    assert H.resolve_hist_method("auto", lay.sync_bins, lay.phys_rows, 1
                                 ) == "pallas"


# -- the plan records the blocks; the program holds them ---------------

def test_round_plan_records_the_blocks_of_every_build(monkeypatch):
    F, depth = 44, 3
    monkeypatch.setattr(H, "_SCOPED_VMEM", _budget(16))
    m = HistGBT(mesh=local_mesh(1), n_trees=2, max_depth=depth, n_bins=32,
                hist_method="pallas")
    plan = m._round_plan(F)
    assert plan.hist_feature_blocks == ((16, 16, 12),) * depth
    assert m.round_plan["hist_feature_blocks"] == [[16, 16, 12]] * depth
    assert m.round_plan["hist_method"] == ["pallas"] * depth
    assert m.round_plan["hist_features"] == [44, 48]
    assert json.loads(json.dumps(m.round_plan)) == m.round_plan
    # one block: the list says so, for every engine that has blocks
    assert HistGBT(mesh=local_mesh(1), n_trees=2, max_depth=depth,
                   n_bins=32, hist_method="pallas")._round_plan(8) \
        .hist_feature_blocks == ((8,),) * depth
    # the blocks move the plan, hence the program cache's key
    monkeypatch.setattr(H, "_SCOPED_VMEM", _budget(24))
    other = m._round_plan(F)
    assert other.hist_feature_blocks == ((24, 20),) * depth
    assert other != plan
    assert m._round_fn_cache_key(other, 2) != m._round_fn_cache_key(plan, 2)
    monkeypatch.setattr(H, "_SCOPED_VMEM", _budget(16))

    # with the environment unreadable the program traces, and holds one
    # kernel call for every block the plan lists
    hg._ROUND_FN_CACHE.pop(m._round_fn_cache_key(plan, 2), None)
    monkeypatch.setattr(os, "environ", _NoLevers(os.environ))
    fn = m._build_round_fn(plan, 2)
    mesh, n = m.mesh, 64
    row = NamedSharding(mesh, P("data"))
    shapes = (jax.ShapeDtypeStruct((F, n), np.uint8,
                                   sharding=NamedSharding(mesh,
                                                          P(None, "data"))),
              jax.ShapeDtypeStruct((n,), np.float32, sharding=row),
              jax.ShapeDtypeStruct((n,), np.float32, sharding=row),
              jax.ShapeDtypeStruct((n,), np.float32, sharding=row))
    assert _pallas_calls(jax.make_jaxpr(fn)(*shapes).jaxpr) == \
        sum(len(b) for b in plan.hist_feature_blocks) == 9
    text = fn.lower(*shapes).as_text(debug_info=True)
    # what blocking adds runs under its own scope, inside the level's
    assert "dmlc.round.L2.hist/dmlc.hist.fblock" in text


def test_the_other_engine_in_the_record():
    seg = HistGBT(mesh=local_mesh(1), n_trees=2, max_depth=3, n_bins=32)
    seg._round_plan(28)
    assert seg.round_plan["hist_feature_blocks"] == [[]] * 3


# -- a wide staged fit against the plain reference ---------------------

def _limit(name):
    with open(os.path.join(_REPO, "benchmark", "traffic",
                           "boost-r25.json")) as f:
        lim = json.load(f)["limits"][name]
    return lim["limit"] if isinstance(lim, dict) else lim


@pytest.fixture(scope="module")
def wide_fit():
    """F = 300 in blocks of 104, 104, 92 (the last no multiple of 8), the
    staged round with every level's histogram a Pallas kernel
    (interpreted), on the benchmark's own data rule."""
    sys.path.insert(0, _REPO)
    from benchmark import datagen

    mp = pytest.MonkeyPatch()
    mp.setattr(H, "_SCOPED_VMEM", _budget(104))
    try:
        cfg = {"rows": 6000, "features": 300, "max_depth": 4, "n_bins": 64,
               "learning_rate": 0.3, "reg_lambda": 1.0,
               "min_child_weight": 1.0, "base_score": 0.0, "n_summary": 512}
        X, y = datagen.higgs_like(cfg["rows"], cfg["features"], 7)
        m = HistGBT(mesh=local_mesh(1), n_trees=3, max_depth=cfg["max_depth"],
                    n_bins=cfg["n_bins"], hist_method="pallas")
        handle = m.make_device_data(X, y)
        m.fit_device(handle)
        first = [{k: np.asarray(v) for k, v in t.items()} for t in m.trees]
        m.fit_device(handle)
        again = [{k: np.asarray(v) for k, v in t.items()} for t in m.trees]
        bins_t = np.asarray(handle["bins_t"])[:, :len(y)]
        yield cfg, X, y, m, first, again, bins_t
    finally:
        mp.undo()


def test_wide_fit_runs_the_staged_round_in_blocks(wide_fit):
    cfg, X, y, m, trees, again, bins_t = wide_fit
    assert m.round_plan["hist_method"] == ["pallas"] * cfg["max_depth"]
    assert m.round_plan["hist_feature_blocks"] == \
        [[104, 104, 92]] * cfg["max_depth"]
    # a split in the last block, past its last whole group of 8, would
    # show a block that lost its tail; the label lives in columns 0..4,
    # so look at the histogram the last block built instead
    g = (0.5 - y).astype(np.float32)
    hist = np.asarray(H.build_histogram(
        jnp.asarray(bins_t), jnp.zeros(len(y), jnp.int32), jnp.asarray(g),
        jnp.ones(len(y), jnp.float32), 1, cfg["n_bins"], "pallas",
        transposed=True))
    assert np.allclose(hist[1].sum(axis=-1), len(y))     # every feature
    assert np.array_equal(
        hist[1, 0, 299], np.bincount(bins_t[299], minlength=cfg["n_bins"]))


def test_wide_fit_against_the_plain_reference(wide_fit):
    from benchmark import checks

    cfg, X, y, m, trees, again, bins_t = wide_fit
    got = checks.boost_tree_numbers(bins_t, y, trees, cfg)
    # tree 1's gradients are rounded to bfloat16 into the kernels: its
    # limit is the wider one (the mix file gives each limit's reason)
    for name in ("tree0.root_gain_gap", "tree0.reported_gain_gap",
                 "tree0.leaf_gap", "tree1.leaf_gap"):
        assert got[name] <= _limit(name), (name, got)
    assert checks.trees_differ(trees, again) == _limit("ops_trees_differ")
    assert checks.bins_mismatches(X[:512], bins_t[:, :512],
                                  np.asarray(m.cuts)) == 0
    # the root split is on a column that carries the label
    assert int(trees[0]["feat"][0, 0]) < 5


@pytest.mark.parametrize("precision, fails", [
    ("bfloat16", "tree0.leaf_gap"), ("float8", "tree1.leaf_gap")])
def test_wide_fit_controls_leave_a_limit(wide_fit, precision, fails):
    from benchmark import checks

    cfg, X, y, m, trees, again, bins_t = wide_fit
    control = checks.control_trees(bins_t, y, trees, cfg, precision)
    got = checks.boost_tree_numbers(bins_t, y, control, cfg)
    assert got[fails] > _limit(fails), got
