"""Histograms in node blocks (ISSUE 37).

A build of more nodes than the Pallas kernel's VMEM budgets admit in one
call (more than 32 at 256 bins: the last level of a depth-8 tree) is
built block by block: the same kernel once per block of nodes over all
rows, the block's node ids mapped to ``0..nb-1`` and every other row to
``-1``, the histograms joined on the node axis.

* a node-blocked build equals the unblocked build byte for byte, with
  real (inexact) gradients, also where a block factors the bins with
  another ``lo`` than the whole build does: a node's sums are made of the
  same operations in the same order either way;
* one kernel call a block; node and feature blocks compose; a packed
  layout, never cut on features, is cut on nodes;
* the node block is the whole build exactly where the gate said yes
  (``_pallas_ok``, which this PR leaves word for word), and there the
  build traces ``_hist_pallas`` and nothing else: the parent's program;
* ``auto`` on a TPU resolves to ``pallas`` for every plain matrix;
* ``HistGBT.round_plan`` records the node blocks of every build, and the
  trees of a fit whose deep levels are node-blocked are the trees of a
  fit that builds them in one call.

The blocks of most tests come from a capped gate (``_pallas_ok`` refusing
more than ``cap`` nodes): blocks follow the gate, nothing else selects
them.
"""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dmlc_core_tpu.models import HistGBT  # noqa: E402
from dmlc_core_tpu.ops import binlayout as bl  # noqa: E402
from dmlc_core_tpu.ops import histogram as H  # noqa: E402
from dmlc_core_tpu.parallel.mesh import local_mesh  # noqa: E402

from test_hist_feature_blocks import _budget, _pallas_calls  # noqa: E402

_GATE = H._pallas_ok


def _cap(monkeypatch, cap):
    """The gate, refusing a call of more than ``cap`` nodes."""
    monkeypatch.setattr(
        H, "_pallas_ok",
        lambda n_bins, n_features, n_nodes=1, bins_itemsize=1, tile_rows=0,
        n_class=1: _GATE(n_bins, n_features, n_nodes, bins_itemsize,
                         tile_rows, n_class) if n_nodes <= cap else 0)


def _rows(F, n_nodes, n_bins, n=700, seed=0):
    rng = np.random.default_rng(seed + 131 * F + n_nodes)
    bins_t = rng.integers(0, n_bins, size=(F, n)).astype(np.uint8)
    node = rng.integers(0, n_nodes, size=n).astype(np.int32)
    node[::7] = -1                          # padded / right-child rows
    g = rng.normal(size=n).astype(np.float32)
    h = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    return bins_t, node, g, h


def _blocks_of(n_nodes, nb):
    return (nb,) * (n_nodes // nb) + ((n_nodes % nb,) if n_nodes % nb else ())


# -- blocked == unblocked, bit for bit ---------------------------------

@pytest.mark.parametrize("transposed", [True, False],
                         ids=["feature_major", "row_major"])
@pytest.mark.parametrize("F, n_bins, n_nodes, cap", [
    (5, 64, 2, 1), (5, 64, 8, 2), (28, 64, 16, 4), (12, 64, 64, 16),
    (9, 64, 128, 32), (7, 64, 6, 4), (7, 64, 100, 32),
    # 256 bins, the measured table: a block of 2 factors the bins with
    # lo = 32, of 4 with 64, the whole build of 8 or more with 128
    (5, 256, 8, 2), (5, 256, 16, 4), (6, 256, 64, 32), (5, 256, 20, 16)])
def test_node_blocked_build_is_the_unblocked_build(F, n_bins, n_nodes, cap,
                                                   transposed, monkeypatch):
    bins_t, node, g, h = _rows(F, n_nodes, n_bins)
    args = [jnp.asarray(bins_t if transposed else bins_t.T),
            jnp.asarray(node), jnp.asarray(g), jnp.asarray(h)]
    # the unblocked build: the kernel itself, past any gate
    whole = np.asarray(H._hist_pallas(*args, n_nodes, n_bins,
                                      transposed=transposed))
    _cap(monkeypatch, cap)
    nb = 1 << (cap.bit_length() - 1)
    assert H.hist_node_blocks(n_bins, F, n_nodes) == _blocks_of(n_nodes, nb)
    got = np.asarray(H.build_histogram(*args, n_nodes, n_bins, "pallas",
                                       transposed=transposed))
    assert got.shape == (2, n_nodes, F, n_bins)
    assert got.tobytes() == whole.tobytes()
    assert got.any()
    # rows at -1 are in no block's histogram
    live = node >= 0
    assert np.allclose(got[1].sum(axis=(0, 2)), h[live].sum(), rtol=1e-2)


def test_node_blocks_over_several_row_tiles(monkeypatch):
    """More rows than one 16,384-row tile: a block's accumulation over
    the tiles is the whole build's."""
    F, n_bins, n_nodes = 3, 64, 8
    bins_t, node, g, h = _rows(F, n_nodes, n_bins, n=2 * H._TILE_ROWS + 77)
    whole = np.asarray(H._hist_pallas(bins_t, node, g, h, n_nodes, n_bins,
                                      transposed=True))
    _cap(monkeypatch, 2)
    got = np.asarray(H.build_histogram(bins_t, node, g, h, n_nodes, n_bins,
                                       "pallas", transposed=True))
    assert got.tobytes() == whole.tobytes()


def test_through_the_staged_levels_entry_point(monkeypatch):
    F, n_bins, n_prev = 6, 64, 16
    bins_t, node, g, h = _rows(F, n_prev, n_bins)
    feat = jnp.zeros(node.size, jnp.int32)
    thr = jnp.full(node.size, n_bins // 2, jnp.int32)
    whole, nd0 = H.descend_histogram(bins_t, node, feat, thr, g, h, n_prev,
                                     n_bins, "pallas")
    _cap(monkeypatch, 4)
    left, nd1 = H.descend_histogram(bins_t, node, feat, thr, g, h, n_prev,
                                    n_bins, "pallas")
    assert np.asarray(left).tobytes() == np.asarray(whole).tobytes()
    assert np.array_equal(nd0, nd1)


# -- one kernel call a block; blocks compose ----------------------------

def test_a_node_blocked_build_is_one_kernel_call_a_block(monkeypatch):
    _cap(monkeypatch, 4)
    bins_t, node, g, h = _rows(12, 16, 64)

    def build(*a):
        return H.build_histogram(*a, 16, 64, "pallas", transposed=True)

    jaxpr = jax.make_jaxpr(build)(bins_t, node, g, h)
    assert _pallas_calls(jaxpr.jaxpr) == 4
    text = jax.jit(build).lower(bins_t, node, g, h).as_text(debug_info=True)
    assert "dmlc.hist.nblock" in text and "dmlc.hist.fblock" not in text


@pytest.mark.parametrize("transposed", [True, False],
                         ids=["feature_major", "row_major"])
def test_node_and_feature_blocks_compose(transposed, monkeypatch):
    F, n_bins, n_nodes = 44, 64, 16
    bins_t, node, g, h = _rows(F, n_nodes, n_bins)
    args = [jnp.asarray(bins_t if transposed else bins_t.T),
            jnp.asarray(node), jnp.asarray(g), jnp.asarray(h)]
    whole = np.asarray(H._hist_pallas(*args, n_nodes, n_bins,
                                      transposed=transposed))
    _cap(monkeypatch, 4)
    monkeypatch.setattr(H, "_SCOPED_VMEM", _budget(16))
    assert H.hist_node_blocks(n_bins, F, n_nodes) == (4,) * 4
    assert H.hist_feature_blocks(n_bins, F, 4) == (16, 16, 12)

    def build(*a):
        return H.build_histogram(*a, n_nodes, n_bins, "pallas",
                                 transposed=transposed)

    assert _pallas_calls(jax.make_jaxpr(build)(*args).jaxpr) == 12
    got = np.asarray(build(*args))
    assert got.tobytes() == whole.tobytes()
    text = jax.jit(build).lower(*args).as_text(debug_info=True)
    assert "dmlc.hist.nblock" in text and "dmlc.hist.fblock" in text


def _packed(n_bins=32):
    rng = np.random.default_rng(0)
    bins_t = rng.integers(0, n_bins, size=(30, 256)).astype(np.uint8)
    bins_t[:20] %= 5
    lay = bl.compute_layout(bl.bin_counts(bins_t, n_bins), 30, n_bins,
                            pack=True)
    assert lay.pairs
    return bins_t, lay


def test_a_packed_layout_is_cut_on_nodes_never_on_features(monkeypatch):
    bins_t, lay = _packed()
    n = bins_t.shape[1]
    rng = np.random.default_rng(1)
    node = rng.integers(-1, 8, size=n).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    phys = np.asarray(bl.pack_matrix(jnp.asarray(bins_t), lay))
    whole = np.asarray(H.build_histogram(phys, node, g, h, 8, 32, "pallas",
                                         transposed=True, layout=lay))
    _cap(monkeypatch, 2)
    assert H.hist_node_blocks(lay.sync_bins, lay.phys_rows, 8,
                              whole=True) == (2,) * 4

    def build(*a):
        return H.build_histogram(*a, 8, 32, "pallas", transposed=True,
                                 layout=lay)

    assert _pallas_calls(jax.make_jaxpr(build)(phys, node, g, h).jaxpr) == 4
    assert np.asarray(build(phys, node, g, h)).tobytes() == whole.tobytes()
    # a node block has to take ALL rows of a packed matrix: where the
    # rows fit at no node count, there is no block
    monkeypatch.setattr(H, "_pallas_ok", _GATE)
    monkeypatch.setattr(H, "_SCOPED_VMEM", _budget(8))
    assert H.hist_node_blocks(lay.sync_bins, lay.phys_rows, 8,
                              whole=True) == ()
    assert H.hist_node_blocks(lay.sync_bins, lay.phys_rows, 8) == (8,)


# -- the node block against the gate -------------------------------------

def _gate_before_issue_37(n_bins, n_features, n_nodes=1, bins_itemsize=1,
                          tile_rows=0):
    """``_pallas_ok`` as PR 36 left it, word for word."""
    lo = H._lo_factor(n_nodes, n_bins)
    hi = -(-n_bins // lo)
    nh = n_nodes * hi
    T = tile_rows or H._TILE_ROWS
    fp_max = min(
        (24 << 20) // (2 * nh * max(lo, 128) * 4),
        ((15 << 20) // T - (120 + 6 * nh + 2 * lo)) // bins_itemsize,
        (H._SCOPED_VMEM // T - H._SCOPED_ROW_RESERVE) // (2 * bins_itemsize))
    if -(-n_features // 8) * 8 <= fp_max:
        return n_features
    return max(fp_max // 8 * 8, 0)


@pytest.mark.parametrize("itemsize", [1, 4])
@pytest.mark.parametrize("n_bins", [32, 64, 128, 200, 256, 512, 4096])
def test_one_block_exactly_where_the_gate_said_yes(n_bins, itemsize):
    for n_nodes in (1, 2, 3, 4, 8, 16, 31, 32, 33, 48, 64, 100, 128, 256):
        for F in (1, 5, 8, 28, 32, 100, 392, 400, 2000):
            was = _gate_before_issue_37(n_bins, F, n_nodes, itemsize)
            assert H._pallas_ok(n_bins, F, n_nodes, itemsize) == was
            for whole in (False, True):
                yes = was >= F if whole else was > 0
                blocks = H.hist_node_blocks(n_bins, F, n_nodes, itemsize,
                                            whole)
                assert (blocks == (n_nodes,)) == yes, (F, n_nodes, blocks)
                if yes:
                    continue
                # else whole blocks of one power of two the gate admits,
                # the next power of two is refused, and the rest
                one = _gate_before_issue_37(n_bins, F, 1, itemsize)
                if not (one >= F if whole else one > 0):
                    assert blocks == ()
                    continue
                nb = blocks[0]
                assert nb & (nb - 1) == 0 and nb < n_nodes
                assert sum(blocks) == n_nodes
                assert all(b == nb for b in blocks[:-1]) and blocks[-1] <= nb
                for n, want in ((nb, True), (2 * nb, False)):
                    if n < n_nodes:
                        ok = _gate_before_issue_37(n_bins, F, n, itemsize)
                        assert (ok >= F if whole else ok > 0) == want


def test_the_shipped_shapes():
    # HIGGS at 256 bins: one call up to the 32 builds of a depth-7 tree's
    # last level; the 64 of depth 8 in two blocks, depth 10's 256 in eight
    for n_build in (1, 2, 4, 8, 16, 32):
        assert H.hist_node_blocks(256, 28, n_build) == (n_build,)
        assert H.hist_node_blocks(256, 2000, n_build) == (n_build,)
    for n_build in (64, 128, 256):
        assert H._pallas_ok(256, 28, n_build) == 0        # one call: no
        assert H.hist_node_blocks(256, 28, n_build) == (32,) * (n_build // 32)
        assert H.hist_node_blocks(256, 2000, n_build) == \
            (32,) * (n_build // 32)
    # each node block in the feature blocks of ITS node count
    assert H.hist_feature_blocks(256, 28, 32) == (28,)
    assert H.hist_feature_blocks(256, 2000, 32) == (200,) * 10
    # not even one node: a bin count whose accumulator no block holds
    assert H._pallas_ok(1 << 17, 28, 1) == 0
    assert H.hist_node_blocks(1 << 17, 28, 4) == ()


@pytest.mark.parametrize("F, n_nodes", [
    (5, 1), (5, 16), (5, 32), (28, 1), (28, 16), (28, 32), (392, 1),
    (392, 16), (200, 32)])
def test_at_one_block_the_build_traces_hist_pallas_alone(F, n_nodes):
    """Every build one call takes: ``build_histogram`` traces to the
    jaxpr of a plain ``_hist_pallas`` call (whose body this PR does not
    touch) — the parent's program, equation for equation."""
    assert H._pallas_ok(256, F, n_nodes) == F
    n = 2 * 256
    shapes = (jnp.zeros((F, n), jnp.uint8), jnp.zeros(n, jnp.int32),
              jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.float32))
    got = jax.make_jaxpr(lambda *a: H.build_histogram(
        *a, n_nodes, 256, "pallas", transposed=True))(*shapes)
    want = jax.make_jaxpr(lambda *a: H._hist_pallas(
        *a, n_nodes, 256, transposed=True))(*shapes)
    assert str(got) == str(want)
    assert "nblock" not in str(got)


# -- auto ------------------------------------------------------------------

@pytest.mark.parametrize("F", [1, 28, 392, 2000, 10000])
def test_auto_on_a_tpu_is_pallas_for_a_plain_matrix(F, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for n_bins in (32, 64, 256, 1024):
        for n_nodes in range(1, 257):
            assert H.resolve_hist_method("auto", n_bins, F, n_nodes) == \
                "pallas", (n_bins, n_nodes)
            assert H.resolve_hist_method("pallas", n_bins, F, n_nodes) == \
                "pallas"


def test_explicit_pallas_raises_where_not_even_one_node_fits(monkeypatch):
    from dmlc_core_tpu.base.logging import Error

    with pytest.raises(Error, match="not even 8 rows, for even one node"):
        H.resolve_hist_method("pallas", 1 << 17, 28, 4)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert H.resolve_hist_method("auto", 1 << 17, 28, 4) == "segment"
    # the other engine is returned as asked, at any node count
    assert H.resolve_hist_method("segment", 256, 28, 64) == "segment"


# -- the plan records the node blocks ---------------------------------------

@pytest.mark.parametrize("F, fblocks_deep", [
    (28, [28]), (2000, [200] * 10)])
@pytest.mark.parametrize("depth", [6, 8, 10])
def test_round_plan_records_the_node_blocks(depth, F, fblocks_deep,
                                            monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    m = HistGBT(mesh=local_mesh(1), n_trees=2, max_depth=depth, n_bins=256)
    plan = m._round_plan(F)
    builds = [1] + [1 << (lv - 1) for lv in range(1, depth)]
    assert m.round_plan["hist_method"] == ["pallas"] * depth
    assert m.round_plan["hist_node_blocks"] == [
        [nb] if nb <= 32 else [32] * (nb // 32) for nb in builds]
    assert plan.hist_node_blocks == tuple(
        tuple(b) for b in m.round_plan["hist_node_blocks"])
    shallow = [F] if F == 28 else [392] * 5 + [40]
    assert m.round_plan["hist_feature_blocks"] == [
        shallow if nb < 32 else fblocks_deep for nb in builds]
    assert json.loads(json.dumps(m.round_plan)) == m.round_plan


def test_the_other_engine_has_no_node_blocks():
    seg = HistGBT(mesh=local_mesh(1), n_trees=2, max_depth=8, n_bins=256)
    seg._round_plan(28)
    assert seg.round_plan["hist_method"] == ["segment"] * 8
    assert seg.round_plan["hist_node_blocks"] == [[]] * 8


def test_the_node_blocks_move_the_plan_and_the_cache_key(monkeypatch):
    m = HistGBT(mesh=local_mesh(1), n_trees=2, max_depth=5, n_bins=64,
                hist_method="pallas")
    plan = m._round_plan(9)
    assert plan.hist_node_blocks == ((1,), (1,), (2,), (4,), (8,))
    _cap(monkeypatch, 2)
    other = m._round_plan(9)
    assert other.hist_node_blocks == ((1,), (1,), (2,), (2, 2), (2,) * 4)
    assert other != plan
    assert m._round_fn_cache_key(other, 2) != m._round_fn_cache_key(plan, 2)


# -- a deep fit in node blocks is the fit in one call ------------------------

def _sha(trees):
    d = hashlib.sha256()
    for t in trees:
        for k in sorted(t):
            d.update(np.ascontiguousarray(np.asarray(t[k])).tobytes())
    return d.hexdigest()


@pytest.fixture(scope="module")
def deep_data():
    from benchmark import datagen

    return datagen.higgs_like(5000, 6, 37)


@pytest.mark.parametrize("depth, cap", [(5, 2), (6, 4), (8, 32)])
def test_trees_of_a_node_blocked_fit_are_the_one_call_fits(deep_data, depth,
                                                           cap, monkeypatch):
    """The staged round, every level's histogram a Pallas kernel
    (interpreted), real gradients: sha256 over every array of every
    tree.  ``(8, 32)`` is the shipped gate's own answer at 256 bins."""
    X, y = deep_data
    n_bins = 256 if cap == 32 else 32
    kw = dict(mesh=local_mesh(1), n_trees=2, max_depth=depth, n_bins=n_bins,
              learning_rate=0.1, hist_method="pallas")

    def fit():
        m = HistGBT(**kw)
        m.fit_device(m.make_device_data(X, y))
        return m

    if cap == 32:
        # the shipped gate blocks L7; lift term (b) for the twin
        blocked = fit()
        monkeypatch.setattr(H, "_pallas_ok",
                            lambda n_bins, F, *a, **k: F)
        whole = fit()
    else:
        whole = fit()
        _cap(monkeypatch, cap)
        blocked = fit()
    builds = [1] + [1 << (lv - 1) for lv in range(1, depth)]
    assert whole.round_plan["hist_node_blocks"] == [[nb] for nb in builds]
    assert blocked.round_plan["hist_node_blocks"][-1] == \
        [cap] * (builds[-1] // cap)
    assert len(blocked.round_plan["hist_node_blocks"][-1]) >= 2
    assert blocked.round_plan["hist_method"] == ["pallas"] * depth
    assert _sha(blocked.trees) == _sha(whole.trees)
    assert np.array_equal(blocked.predict(X[:512]), whole.predict(X[:512]))
