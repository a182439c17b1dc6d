"""Histograms in class blocks (ISSUE 49).

A multiclass round grows its K trees level by level together, and a
level's K histograms are ONE ``build_histogram`` with a class axis:
``node_id`` / ``grad`` / ``hess`` class-major ``[K, n]``.  The Pallas
engine builds it one kernel call per BLOCK of classes: per feature the
kernel reads the bins block once, builds the right one-hot ``[lo, T]``
once, and lays the classes' left one-hots — each from its own class's
node ids, scaled by its own gradients — one under another to the MXU's
128 rows, for one dot.

* a stacked build equals K single-class builds byte for byte, with real
  (inexact) gradients, rows at node -1, a feature count that is no
  multiple of 8, blocks that do not fill the 128 rows, and where the
  stack factors the bins with another ``lo`` than a single call does: a
  cell is the sum of the same products over the same rows in the same
  tile order, whoever shares its dot;
* how many classes a call takes comes from shapes alone
  (``hist_class_blocks``): the largest ``kb`` with ``kb x A <= 128``
  inside the gate's budgets; one class is ``(1,)`` at every shape;
* one kernel call a class block; class blocks compose with feature
  blocks (a stacked call cut on features) and with node blocks (a build
  one call does not take is not stacked: a class at a time, in node
  blocks);
* with no class axis the build traces what it traced before this PR:
  the jaxpr's text is held by its sha256 on the parent commit;
* ``HistGBT.round_plan`` records the class blocks of every build.
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

from test_hist_feature_blocks import _pallas_calls  # noqa: E402
from test_hist_node_blocks import _cap, _packed  # noqa: E402


def _rows(F, n_nodes, n_bins, K, n=700, seed=0):
    rng = np.random.default_rng(seed + 131 * F + 17 * n_nodes + K)
    bins_t = rng.integers(0, n_bins, size=(F, n)).astype(np.uint8)
    node = rng.integers(0, n_nodes, size=(K, n)).astype(np.int32)
    node[:, ::7] = -1                       # padded / right-child rows
    node[K // 2, 1::3] = -1                 # and one class with more
    g = rng.normal(size=(K, n)).astype(np.float32)
    h = rng.uniform(0.1, 1.0, size=(K, n)).astype(np.float32)
    return bins_t, node, g, h


def _singles(bins, node, g, h, n_nodes, n_bins, **kw):
    """K builds of one class each: the loop a class axis replaces."""
    return np.stack([
        np.asarray(H.build_histogram(bins, node[c], g[c], h[c], n_nodes,
                                     n_bins, "pallas", **kw))
        for c in range(node.shape[0])])


def _blocks_of(K, kb):
    return (kb,) * (K // kb) + ((K % kb,) if K % kb else ())


# -- stacked == K single builds, bit for bit ----------------------------

#: (n_build, classes a call at 256 bins): the left operand of one class
#: is A = 2 x n_build x hi rows at the stack's lo (32 / 64 / 128 / 128)
_KB_256 = {1: 8, 2: 8, 8: 4, 16: 2}


@pytest.mark.parametrize("K", [1, 2, 3, 7])
@pytest.mark.parametrize("n_build", [1, 2, 8, 16])
def test_stacked_build_is_k_single_class_builds(K, n_build):
    F, n_bins = 13, 256
    bins_t, node, g, h = _rows(F, n_build, n_bins, K)
    blocks = H.hist_class_blocks(n_bins, F, n_build, K)
    assert blocks == _blocks_of(K, min(K, _KB_256[n_build]))
    got = np.asarray(H.build_histogram(bins_t, node, g, h, n_build, n_bins,
                                       "pallas", transposed=True))
    assert got.shape == (K, 2, n_build, F, n_bins)
    want = _singles(bins_t, node, g, h, n_build, n_bins, transposed=True)
    assert got.tobytes() == want.tobytes()
    assert got.any()
    # rows at -1 are in no class's histogram
    for c in range(K):
        live = node[c] >= 0
        assert np.allclose(got[c, 1].sum(axis=(0, 2)), h[c][live].sum(),
                           rtol=1e-2)


@pytest.mark.parametrize("n_bins, n_nodes, K, blocks", [
    (64, 8, 5, (5,)),          # lo 64, nh 8: five classes, 80 rows
    (64, 16, 5, (4, 1)),       # A = 32: four fill the array
    (64, 4, 3, (3,)),          # lo 32, hi 2, nh 8
    (64, 1, 3, (1, 1, 1)),     # no aligned lo: a call a class
    (32, 8, 7, (7,)),          # lo 32, hi 1
    (128, 2, 6, (6,)),         # lo 32, hi 4
    (200, 4, 7, (7,))])        # 200 bins: hi·lo = 256, the pads sliced
def test_other_bin_counts(n_bins, n_nodes, K, blocks):
    F = 6
    bins_t, node, g, h = _rows(F, n_nodes, n_bins, K)
    assert H.hist_class_blocks(n_bins, F, n_nodes, K) == blocks
    got = np.asarray(H.build_histogram(bins_t, node, g, h, n_nodes, n_bins,
                                       "pallas", transposed=True))
    want = _singles(bins_t, node, g, h, n_nodes, n_bins, transposed=True)
    assert got.shape == (K, 2, n_nodes, F, n_bins)
    assert got.tobytes() == want.tobytes()


def test_row_major_bins_and_several_row_tiles():
    """More rows than one 16,384-row tile, the matrix given ``[n, F]``: a
    class's accumulation over the tiles is its own build's."""
    F, n_bins, n_build, K = 3, 256, 2, 3
    bins_t, node, g, h = _rows(F, n_build, n_bins, K,
                               n=2 * H._TILE_ROWS + 77)
    got = np.asarray(H.build_histogram(bins_t.T, node, g, h, n_build,
                                       n_bins, "pallas"))
    want = _singles(bins_t.T, node, g, h, n_build, n_bins)
    assert got.tobytes() == want.tobytes()


def test_a_stack_at_another_lo_than_the_single_call():
    """Two and four builds at 256 bins: a single call factors the bins
    with lo = 32 / 64 (the measured table), the stack with 64 / 128 —
    ``lo`` decides which dot a cell sits in, not what is added into it."""
    for n_build, single, stacked in ((2, 32, 64), (4, 64, 128)):
        assert H._lo_factor(n_build, 256) == single
        assert H._lo_stacked(n_build, 256) == stacked
        bins_t, node, g, h = _rows(5, n_build, 256, 3)
        got = np.asarray(H._hist_pallas(bins_t, node, g, h, n_build, 256,
                                        transposed=True))
        for lo in (32, 64, 128):
            one = np.stack([np.asarray(H._hist_pallas(
                bins_t, node[c], g[c], h[c], n_build, 256, H._TILE_ROWS, lo,
                True)) for c in range(3)])
            assert got.tobytes() == one.tobytes(), (n_build, lo)


def test_the_segment_engine_carries_the_class_axis():
    bins_t, node, g, h = _rows(7, 4, 64, 3)
    got = np.asarray(H.build_histogram(bins_t, node, g, h, 4, 64, "segment",
                                       transposed=True))
    want = np.stack([np.asarray(H.build_histogram(
        bins_t, node[c], g[c], h[c], 4, 64, "segment", transposed=True))
        for c in range(3)])
    assert got.shape == (3, 2, 4, 7, 64)
    assert got.tobytes() == want.tobytes()


def test_through_the_staged_levels_entry_point():
    F, n_bins, n_prev, K = 6, 256, 8, 7
    bins_t, node, g, h = _rows(F, n_prev, n_bins, K)
    rng = np.random.default_rng(5)
    feat = rng.integers(0, F, size=node.shape).astype(np.int32)
    thr = rng.integers(0, n_bins, size=node.shape).astype(np.int32)
    left, new = H.descend_histogram(bins_t, node, feat, thr, g, h, n_prev,
                                    n_bins, "pallas")
    assert left.shape == (K, 2, n_prev, F, n_bins)
    for c in range(K):
        l_c, n_c = H.descend_histogram(bins_t, node[c], feat[c], thr[c],
                                       g[c], h[c], n_prev, n_bins, "pallas")
        assert np.asarray(left[c]).tobytes() == np.asarray(l_c).tobytes()
        assert np.array_equal(new[c], n_c)


def test_a_packed_layout_is_stacked_too():
    bins_t, lay = _packed()
    n = bins_t.shape[1]
    rng = np.random.default_rng(1)
    K, n_nodes = 3, 8
    node = rng.integers(-1, n_nodes, size=(K, n)).astype(np.int32)
    g = rng.normal(size=(K, n)).astype(np.float32)
    h = rng.uniform(0.1, 1.0, size=(K, n)).astype(np.float32)
    phys = np.asarray(bl.pack_matrix(jnp.asarray(bins_t), lay))
    assert H.hist_class_blocks(lay.sync_bins, lay.phys_rows, n_nodes, K,
                               whole=True) == (3,)
    got = np.asarray(H.build_histogram(phys, node, g, h, n_nodes, 32,
                                       "pallas", transposed=True,
                                       layout=lay))
    want = _singles(phys, node, g, h, n_nodes, 32, transposed=True,
                    layout=lay)
    assert got.tobytes() == want.tobytes()


# -- one kernel call a class block; blocks compose -------------------------

@pytest.mark.parametrize("K, n_build, calls", [
    (7, 1, 1), (7, 4, 1), (7, 8, 2), (7, 16, 4), (3, 16, 2), (1, 8, 1),
    (7, 32, 7)])
def test_one_kernel_call_a_class_block(K, n_build, calls):
    bins_t, node, g, h = _rows(9, n_build, 256, K, n=256)

    def build(*a):
        return H.build_histogram(*a, n_build, 256, "pallas", transposed=True)

    assert len(H.hist_class_blocks(256, 9, n_build, K)) == calls
    assert _pallas_calls(jax.make_jaxpr(build)(bins_t, node, g, h).jaxpr) \
        == calls
    text = jax.jit(build).lower(bins_t, node, g, h).as_text(debug_info=True)
    # the slabs of classes and the join are named; one block has neither
    assert ("dmlc.hist.cblock" in text) == (calls > 1 or K == 1)
    assert "dmlc_hist" in text


def _stacked_budget(block, n_class):
    """The stacked call's wall under which the double-buffered bins block
    admits ``block`` feature rows at the default tile."""
    return H._TILE_ROWS * (2 * block + H._SCOPED_ROW_RESERVE
                           + n_class * H._STACKED_ROW_RESERVE)


def test_a_stacked_call_is_cut_in_feature_blocks(monkeypatch):
    F, n_bins, n_nodes, K = 44, 64, 8, 3
    bins_t, node, g, h = _rows(F, n_nodes, n_bins, K)
    whole = np.asarray(H.build_histogram(bins_t, node, g, h, n_nodes, n_bins,
                                         "pallas", transposed=True))
    monkeypatch.setattr(H, "_STACKED_VMEM", _stacked_budget(16, K))
    assert H.hist_class_blocks(n_bins, F, n_nodes, K) == (3,)
    assert H.hist_feature_blocks(n_bins, F, n_nodes, n_class=K) == \
        (16, 16, 12)
    assert H.hist_feature_blocks(n_bins, F, n_nodes) == (F,)

    def build(*a):
        return H.build_histogram(*a, n_nodes, n_bins, "pallas",
                                 transposed=True)

    assert _pallas_calls(jax.make_jaxpr(build)(bins_t, node, g, h).jaxpr) \
        == 3
    got = np.asarray(build(bins_t, node, g, h))
    assert got.tobytes() == whole.tobytes()
    assert got.tobytes() == _singles(bins_t, node, g, h, n_nodes, n_bins,
                                     transposed=True).tobytes()
    text = jax.jit(build).lower(bins_t, node, g, h).as_text(debug_info=True)
    assert "dmlc.hist.fblock" in text and "dmlc.hist.nblock" not in text


def test_fewer_classes_a_call_where_the_budgets_say_so(monkeypatch):
    """The stack's wall admits eight feature rows of three classes and
    not of four: seven classes go three, three and one."""
    F, n_bins, n_nodes, K = 8, 64, 8, 7
    assert H.hist_class_blocks(n_bins, F, n_nodes, K) == (7,)
    monkeypatch.setattr(H, "_STACKED_VMEM", _stacked_budget(8, 3))
    assert H._pallas_ok(n_bins, F, n_nodes, n_class=3) == F
    assert H._pallas_ok(n_bins, F, n_nodes, n_class=4) == 0
    assert H.hist_class_blocks(n_bins, F, n_nodes, K) == (3, 3, 1)
    bins_t, node, g, h = _rows(F, n_nodes, n_bins, K)
    got = np.asarray(H.build_histogram(bins_t, node, g, h, n_nodes, n_bins,
                                       "pallas", transposed=True))
    assert got.tobytes() == _singles(bins_t, node, g, h, n_nodes, n_bins,
                                     transposed=True).tobytes()


def test_a_build_in_node_blocks_is_not_stacked(monkeypatch):
    """A build one call does not take goes a class at a time, each in its
    node blocks: cap the gate at 4 nodes a call."""
    F, n_bins, n_nodes, K = 12, 64, 16, 3
    bins_t, node, g, h = _rows(F, n_nodes, n_bins, K)
    whole = _singles(bins_t, node, g, h, n_nodes, n_bins, transposed=True)
    _cap(monkeypatch, 4)
    assert H.hist_node_blocks(n_bins, F, n_nodes) == (4,) * 4
    assert H.hist_class_blocks(n_bins, F, n_nodes, K) == (1,) * 3

    def build(*a):
        return H.build_histogram(*a, n_nodes, n_bins, "pallas",
                                 transposed=True)

    assert _pallas_calls(jax.make_jaxpr(build)(bins_t, node, g, h).jaxpr) \
        == 12
    assert np.asarray(build(bins_t, node, g, h)).tobytes() == \
        whole.tobytes()


# -- the class block from shapes alone ----------------------------------------

def test_the_shipped_shapes():
    # Covertype: seven classes, 54 columns, 256 bins, depth 6
    builds = [1, 1, 2, 4, 8, 16]
    assert [H.hist_class_blocks(256, 54, nb, 7) for nb in builds] == [
        (7,), (7,), (7,), (7,), (4, 3), (2, 2, 2, 1)]
    # the stacked left operand never passes the MXU's 128 rows
    for nb in builds:
        lo = H._lo_stacked(nb, 256)
        A = 2 * nb * (256 // lo)
        assert all(kb * A <= 128 for kb in H.hist_class_blocks(256, 54, nb,
                                                               7))
        assert (nb * (256 // lo)) % 8 == 0
    assert [H._lo_stacked(nb, 256) for nb in builds] == [
        32, 32, 64, 128, 128, 128]
    # deeper: one class's A = 128 fills the array, then node blocks
    assert H.hist_class_blocks(256, 54, 32, 7) == (1,) * 7
    assert H.hist_class_blocks(256, 54, 64, 7) == (1,) * 7
    # a batch of eight classes (the round program's largest)
    assert [H.hist_class_blocks(256, 54, nb, 8) for nb in builds] == [
        (8,), (8,), (8,), (8,), (4, 4), (2, 2, 2, 2)]
    # the stacked call inside its own budgets at Covertype's and at
    # Epsilon's width
    assert H._pallas_ok(256, 54, 8, n_class=4) == 54
    assert H._pallas_ok(256, 54, 1, n_class=7) == 54
    wide = H._pallas_ok(256, 2000, 1, n_class=7)
    assert 0 < wide < 2000 and wide % 8 == 0
    assert sum(H.hist_feature_blocks(256, 2000, 1, n_class=7)) == 2000


@pytest.mark.parametrize("n_bins", [32, 64, 128, 200, 256, 512, 4096])
def test_one_class_is_one_call_at_every_shape(n_bins):
    for n_nodes in (1, 2, 3, 4, 8, 16, 32, 33, 64, 256):
        for F in (1, 5, 28, 54, 392, 2000):
            assert H.hist_class_blocks(n_bins, F, n_nodes, 1) == (1,)


@pytest.mark.parametrize("n_bins", [32, 64, 128, 256, 512])
@pytest.mark.parametrize("K", [2, 3, 7, 8, 30])
def test_the_class_blocks_add_up_and_fit_the_array(n_bins, K):
    for n_nodes in (1, 2, 4, 8, 16, 32, 64):
        for F in (5, 54, 392, 2000):
            blocks = H.hist_class_blocks(n_bins, F, n_nodes, K)
            assert sum(blocks) == K
            kb = blocks[0]
            assert all(b == kb for b in blocks[:-1]) and blocks[-1] <= kb
            if kb == 1:
                continue
            lo = H._lo_stacked(n_nodes, n_bins)
            assert lo and kb * 2 * n_nodes * -(-n_bins // lo) <= H._MXU_ROWS
            assert H._pallas_ok(n_bins, F, n_nodes, n_class=kb) > 0
            assert H.hist_node_blocks(n_bins, F, n_nodes) == (n_nodes,)


# -- no class axis: the parent's program ---------------------------------------

#: sha256 of ``str(jax.make_jaxpr(build_histogram ...))`` on the parent
#: commit (2dd8d8b) at ``(F, n_nodes, n_bins)``, rows 515: the kernel of
#: one class, its pads and its unpack, equation for equation.  A PR that
#: changes the single-class kernel on purpose re-reads these there.
_PARENT_JAXPR = {
    (5, 1, 256):
        "9c01f8cf4a843624d364f56ebc3deb9c6baa47dbeef4bd4f6fcfb23364f33bb0",
    (28, 16, 256):
        "a645491976a3579ac13938f5d1c5ca9f458a1fe973a79cdcd8b5b071007264ba",
    (13, 2, 64):
        "a27396ea213d86442dc450d9fbf59014dda704f47c1910e8ab02c22a351876ea",
    (54, 8, 256):
        "8c975d6d678c51ddb9ac187d18d5bb20a1a0738be2ec880a43dc19601f37229f",
}


@pytest.mark.parametrize("F, n_nodes, n_bins", sorted(_PARENT_JAXPR))
def test_without_a_class_axis_the_build_traces_the_parents_jaxpr(
        F, n_nodes, n_bins):
    n = 2 * 256 + 3
    shapes = (jnp.zeros((F, n), jnp.uint8), jnp.zeros(n, jnp.int32),
              jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.float32))
    text = str(jax.make_jaxpr(lambda *a: H.build_histogram(
        *a, n_nodes, n_bins, "pallas", transposed=True))(*shapes))
    assert "cblock" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == \
        _PARENT_JAXPR[(F, n_nodes, n_bins)]


def test_a_class_axis_of_one_is_the_single_call():
    """``[1, n]``: a block of one class is cut out and built by the call
    of one class — the same kernel, the same bytes."""
    bins_t, node, g, h = _rows(11, 4, 256, 1)
    one = jax.make_jaxpr(lambda *a: H.build_histogram(
        *a, 4, 256, "pallas", transposed=True))
    assert _pallas_calls(one(bins_t, node, g, h).jaxpr) == 1
    got = np.asarray(H.build_histogram(bins_t, node, g, h, 4, 256, "pallas",
                                       transposed=True))
    want = np.asarray(H.build_histogram(bins_t, node[0], g[0], h[0], 4, 256,
                                        "pallas", transposed=True))
    assert got.shape == (1,) + want.shape
    assert got[0].tobytes() == want.tobytes()


# -- the plan records the class blocks -------------------------------------------

def test_round_plan_records_the_class_blocks(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    m = HistGBT(mesh=local_mesh(1), n_trees=2, max_depth=6, n_bins=256,
                objective="multi:softmax", num_class=7)
    plan = m._round_plan(54)
    assert m.round_plan["hist_method"] == ["pallas"] * 6
    assert m.round_plan["hist_class_blocks"] == [
        [7], [7], [7], [7], [4, 3], [2, 2, 2, 1]]
    assert plan.hist_class_blocks == tuple(
        tuple(b) for b in m.round_plan["hist_class_blocks"])
    assert m.round_plan["hist_node_blocks"] == [[1], [1], [2], [4], [8], [16]]
    assert json.loads(json.dumps(m.round_plan)) == m.round_plan
    # ten classes grow in two batches of five
    m10 = HistGBT(mesh=local_mesh(1), n_trees=2, max_depth=6, n_bins=256,
                  objective="multi:softmax", num_class=10)
    m10._round_plan(54)
    assert m10.round_plan["hist_class_blocks"] == [
        [5], [5], [5], [5], [4, 1], [2, 2, 1]]
    # one tree a round: one class a call at every level, at any depth
    for depth in (6, 8):
        b = HistGBT(mesh=local_mesh(1), n_trees=2, max_depth=depth,
                    n_bins=256)
        b._round_plan(28)
        assert b.round_plan["hist_class_blocks"] == [[1]] * depth


def test_the_other_engine_has_no_class_blocks():
    seg = HistGBT(mesh=local_mesh(1), n_trees=2, max_depth=4, n_bins=256,
                  objective="multi:softmax", num_class=7)
    seg._round_plan(28)
    assert seg.round_plan["hist_method"] == ["segment"] * 4
    assert seg.round_plan["hist_class_blocks"] == [[]] * 4


def test_the_class_blocks_move_the_plan_and_the_cache_key(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kw = dict(mesh=local_mesh(1), n_trees=2, max_depth=5, n_bins=256,
              objective="multi:softmax")
    m = HistGBT(num_class=7, **kw)
    plan = m._round_plan(9)
    assert plan.hist_class_blocks == ((7,), (7,), (7,), (7,), (4, 3))
    monkeypatch.setattr(H, "_MXU_ROWS", 64)
    other = m._round_plan(9)
    assert other.hist_class_blocks == ((4, 3), (4, 3), (4, 3), (4, 3),
                                       (2, 2, 2, 1))
    assert other != plan
    assert m._round_fn_cache_key(other, 2) != m._round_fn_cache_key(plan, 2)
