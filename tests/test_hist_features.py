"""The Pallas histogram kernels emit a dot per REAL feature (ISSUE 34).

The bin matrix is padded to a multiple of 8 rows so that the kernels
can slice it in aligned groups; until ISSUE 34 every pad FEATURE got
its compare, scalings and MXU dot like a real one (HIGGS: 4 of 32).

* ``_hist_pallas`` equals the scatter-add engine at feature counts on
  both sides of a group boundary, transposed and not, with masked rows;
* the staged level (``descend_histogram``) equals a numpy descend and
  the loop oracle over its left children, plain, packed and bundled;
* a fit at F = 28 equals the fit of the same columns zero-padded to 32
  by the caller — the arithmetic of the kernels before ISSUE 34;
* the dots a row tile issues are counted off the kernel's jaxpr: F, not
  the padded count, and ``HistGBT.round_plan`` records the pair.

The gradients of the kernel-level cases are multiples of 1/8 and 1/4,
exact in bfloat16, and their float32 sums are exact in any order: the
engines are compared bit for bit, not within a tolerance.
"""

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

from test_binpack import _exclusive_bins, _spread_bins  # noqa: E402

B, TILE = 64, 256


def _rows(F, n_nodes, n=700, seed=0):
    """Feature-major bins, node ids with masked rows, exact gradients."""
    rng = np.random.default_rng(seed + 131 * F + n_nodes)
    bins_t = rng.integers(0, B, size=(F, n)).astype(np.uint8)
    node = rng.integers(0, n_nodes, size=n).astype(np.int32)
    node[::7] = -1                          # padded / pruned rows
    g = (rng.integers(-16, 17, size=n) / 8).astype(np.float32)
    h = (rng.integers(1, 9, size=n) / 4).astype(np.float32)
    return bins_t, node, g, h


@pytest.mark.parametrize("transposed", [True, False],
                         ids=["feature_major", "row_major"])
@pytest.mark.parametrize("n_nodes", [1, 4, 16])
@pytest.mark.parametrize("F", [5, 8, 12, 28, 31, 32, 39])
def test_hist_pallas_equals_segment(F, n_nodes, transposed):
    bins_t, node, g, h = _rows(F, n_nodes)
    # 700 rows over 256-row tiles: three tiles into one block, the last
    # one partly row padding (node = -1), whatever F's feature padding
    got = np.asarray(H._hist_pallas(
        jnp.asarray(bins_t if transposed else bins_t.T), jnp.asarray(node),
        jnp.asarray(g), jnp.asarray(h), n_nodes, B, TILE, 0, transposed))
    want = np.asarray(H._hist_segment(
        jnp.asarray(bins_t.T), jnp.asarray(node), jnp.asarray(g),
        jnp.asarray(h), n_nodes, B))
    assert got.shape == (2, n_nodes, F, B)
    assert np.array_equal(got, want)
    if F == 5 and n_nodes == 1:             # the oracle itself, once
        np.testing.assert_array_equal(
            want, H.reference_histogram(bins_t.T, node, g, h, n_nodes, B))


def _level_against_numpy(bins_t, node, g, h, n_prev, n_bins, rng,
                         layout=None):
    """One staged level by ``method="pallas"`` against the plain
    reference: a numpy descend (``bin > thr``) for the node ids, the
    loop oracle over the left children for the histograms."""
    F = bins_t.shape[0]
    feat = rng.integers(0, F, size=n_prev).astype(np.int32)[
        np.maximum(node, 0)]
    feat[-3:] = F - 1                       # the last real feature is a key
    thr = rng.integers(0, n_bins, size=node.size).astype(np.int32)
    mat = (bins_t if layout is None
           else np.asarray(bl.pack_matrix(jnp.asarray(bins_t), layout)))
    left, new_node = H.descend_histogram(
        *(jnp.asarray(a) for a in (mat, node, feat, thr, g, h)),
        n_prev, n_bins, "pallas", layout=layout)
    if layout is not None:
        left = bl.unbundle_hist(left, layout, n_bins)
    row_bin = bins_t[feat, np.arange(node.size)].astype(np.int32)
    want_node = np.where(node >= 0, 2 * node + (row_bin > thr), -1)
    lefts = np.where((node >= 0) & (want_node % 2 == 0), want_node >> 1, -1)
    assert np.array_equal(np.asarray(new_node), want_node)
    assert np.array_equal(
        np.asarray(left),
        H.reference_histogram(bins_t.T, lefts, g, h, n_prev, n_bins))
    assert np.asarray(left).any() and (lefts >= 0).any()


@pytest.mark.parametrize("n_prev", [1, 4])
@pytest.mark.parametrize("F", [28, 31, 39])
def test_staged_level_equals_numpy_reference(F, n_prev):
    bins_t, node, g, h = _rows(F, n_prev, n=701, seed=5)
    _level_against_numpy(bins_t, node, g, h, n_prev, B,
                         np.random.default_rng(F))


def _packed_rows(n, n_bins, rng):
    """Nine features, four of them narrow with SPREAD bin ids: the
    compact remap, then nibble pairs."""
    bins_t = _spread_bins(rng, n, 9, n_bins, narrow=(1, 4, 7, 8))
    lay = bl.compute_layout(bl.bin_counts(bins_t, n_bins), 9, n_bins)
    assert lay.pairs
    return bins_t, lay


def _bundled_rows(n, n_bins, rng):
    """One wide feature and two near-one-hot ones that never leave
    their default bin together: one bundle."""
    bins_t = _exclusive_bins(rng, n, n_bins)
    counts = bl.bin_counts(bins_t, n_bins)
    lay = bl.compute_layout(
        counts, 3, n_bins, bundles=bl.detect_bundles(bins_t, counts, n_bins))
    assert lay.has_bundles
    return bins_t, lay


@pytest.mark.parametrize("n_prev", [1, 2])
@pytest.mark.parametrize("rows_of", [_packed_rows, _bundled_rows],
                         ids=["packed", "bundled"])
def test_staged_level_equals_numpy_reference_through_a_layout(rows_of,
                                                              n_prev):
    rng = np.random.default_rng(17 + n_prev)
    bins_t, lay = rows_of(701, 32, rng)
    _, node, g, h = _rows(bins_t.shape[0], n_prev, n=701, seed=9)
    _level_against_numpy(bins_t, node, g, h, n_prev, 32, rng, layout=lay)


# -- what a row tile issues, counted off the jaxpr ---------------------

def _dots(jaxpr):
    """``dot_general``s one execution of ``jaxpr`` issues: a loop's body
    counts once per trip (a ``fori_loop`` over static bounds is a scan)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            total += 1
            continue
        assert eqn.primitive.name != "while", "a trip count nobody can read"
        trips = eqn.params.get("length", 1) if eqn.primitive.name == "scan" \
            else 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += trips * _dots(sub)
    return total


def _kernel_dots(fn, *args):
    """Dots per row tile of the one ``pallas_call`` that ``fn`` traces."""
    calls = [e for e in jax.make_jaxpr(fn)(*args).eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    return _dots(calls[0].params["jaxpr"])


def _shapes(rows, n=2 * TILE):
    return (jnp.zeros((rows, n), jnp.uint8), jnp.zeros(n, jnp.int32),
            jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.float32))


@pytest.mark.parametrize("F", [5, 8, 28, 31, 32, 39])
def test_hist_kernel_issues_one_dot_per_real_feature(F):
    bins_t, node, g, h = _shapes(F)
    got = _kernel_dots(
        lambda *a: H._hist_pallas.__wrapped__(*a, 4, B, TILE, 0, True),
        bins_t, node, g, h)
    assert (got, -(-F // 8) * 8) == H.hist_feature_dots(F)
    assert got == F


def test_packed_layout_skips_the_pad_rows_of_its_unpacked_region():
    # four narrow features nibble-packed into two byte rows (one padded
    # group of 8: 16 logical rows), two wide ones after them: 10
    # physical rows in a block of 16
    rng = np.random.default_rng(0)
    bins_t = rng.integers(0, 32, size=(6, 256)).astype(np.uint8)
    bins_t[:4] %= 5
    lay = bl.compute_layout(bl.bin_counts(bins_t, 32), 6, 32, pack=True)
    assert lay.pairs and (lay.packed_rows, lay.phys_rows) == (8, 10)
    phys, node, g, h = _shapes(lay.phys_rows)
    got = _kernel_dots(
        lambda *a: H._hist_pallas.__wrapped__(
            *a, 4, lay.sync_bins, TILE, 0, True, lay), phys, node, g, h)
    assert H.hist_feature_dots(6, lay) == (18, 24)
    assert got == 18


def test_round_plan_records_the_pair():
    m = HistGBT(mesh=local_mesh(1), n_trees=2, max_depth=3, n_bins=32)
    assert m._round_plan(28).describe()["hist_features"] == [28, 32]
    assert m._round_plan(32).describe()["hist_features"] == [32, 32]
    assert m.round_plan["hist_features"] == [32, 32]


# -- a fit: the caller's own zero columns against the kernel's ---------

def test_fit_equals_fit_of_zero_padded_features(tmp_path):
    """With four zero columns appended by the CALLER the kernels build
    32 features, the four constant ones like any other (no group has a
    tail): the kernels' arithmetic before ISSUE 34.  A constant feature
    is never split on, so the trees must be the same bytes."""
    rng = np.random.default_rng(3)
    X = rng.normal(size=(1201, 28)).astype(np.float32)
    y = (X[:, 0] - 0.7 * X[:, 27] + 0.3 * X[:, 13] > 0).astype(np.float32)
    kw = dict(n_trees=3, max_depth=4, n_bins=32, hist_method="pallas",
              objective="binary:logistic", learning_rate=0.3)

    def saved(X, name):
        m = HistGBT(mesh=local_mesh(1), **kw)
        m.fit(X, y)
        assert m.round_plan["hist_features"] == [X.shape[1], 32]
        m.cuts = m.cuts[:28]                 # the file holds the cuts too
        m.save_model(str(tmp_path / name))
        return (tmp_path / name).read_bytes(), m

    b28, m28 = saved(X, "f28.gbt")
    b32, _ = saved(np.pad(X, ((0, 0), (0, 4))), "f32.gbt")
    assert b28 == b32
    assert any(np.asarray(t["feat"]).max() == 27 for t in m28.trees)


# -- the chip's compiler, without the chip ------------------------------

@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    from jax.sharding import Mesh
    return Mesh(np.asarray(topo.devices), ("data",))


def _compiled_for_the_chip(fn, *shapes):
    """XLA:TPU + Mosaic on ``fn`` at ``shapes``; the compiled program.
    A compile for a described chip cannot be read back from the
    persistent cache: it is kept out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return jax.jit(fn).lower(*shapes).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _compile_for_the_chip(fn, *shapes):
    return _compiled_for_the_chip(fn, *shapes).as_text()


@pytest.mark.parametrize("F", [28, 31])
def test_mosaic_compiles_the_tail_at_the_deepest_level(F, one_chip,
                                                       monkeypatch):
    """The static tail unrolls up to seven more dots beside the loop's
    eight: Mosaic has to take it inside scoped VMEM at the deepest level
    of a depth-6 tree (16 built nodes, 256 bins, the real row tile)."""
    monkeypatch.setattr(H, "pallas_interpret", lambda: False)
    n, N, bins = 2 * H._TILE_ROWS, 16, 256

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compile_for_the_chip(
        lambda b, nd, g, h: H._hist_pallas.__wrapped__(
            b, nd, g, h, N, bins, H._TILE_ROWS, 0, True),
        S((F, n), jnp.uint8), S((n,), jnp.int32), S((n,), jnp.float32),
        S((n,), jnp.float32))
    assert "dmlc_hist" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("n_nodes", [1, 8, 16])
def test_mosaic_takes_the_largest_feature_block(n_nodes, one_chip,
                                                monkeypatch):
    """ISSUE 35: the block ``_pallas_ok`` gives at 256 bins is 392 rows
    at every build of a depth-6 tree, and the chip's compiler takes a
    kernel of that block at the real row tile (it takes 416 / 392 / 408
    at these three builds and refuses 8 more)."""
    monkeypatch.setattr(H, "pallas_interpret", lambda: False)
    fb = H._pallas_ok(256, 2000, n_nodes)
    assert fb == 392
    n = 2 * H._TILE_ROWS

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compile_for_the_chip(
        lambda b, nd, g, h: H.build_histogram(b, nd, g, h, n_nodes, 256,
                                              "pallas", transposed=True),
        S((fb, n), jnp.uint8), S((n,), jnp.int32), S((n,), jnp.float32),
        S((n,), jnp.float32))
    assert text.count("tpu_custom_call") >= 1 and "dmlc_hist" in text


def test_mosaic_refuses_what_the_gate_used_to_admit(one_chip, monkeypatch):
    """Why ``_pallas_ok`` counts the bins block twice: the gate as PR 34
    left it admitted a root build of up to 728 feature rows, and the
    compiler refuses one of 424 — the pipeline double-buffers the
    ``[Fp, T]`` block.  The blocked build of the same matrix compiles."""
    monkeypatch.setattr(H, "pallas_interpret", lambda: False)
    n, F = 2 * H._TILE_ROWS, 424

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = (S((F, n), jnp.uint8), S((n,), jnp.int32),
              S((n,), jnp.float32), S((n,), jnp.float32))
    with pytest.raises(Exception, match="vmem"):
        _compile_for_the_chip(
            lambda b, nd, g, h: H._hist_pallas.__wrapped__(
                b, nd, g, h, 1, 256, H._TILE_ROWS, 0, True), *shapes)
    assert H.hist_feature_blocks(256, F, 1) == (392, 32)
    text = _compile_for_the_chip(
        lambda b, nd, g, h: H.build_histogram(b, nd, g, h, 1, 256,
                                              "pallas", transposed=True),
        *shapes)
    assert text.count("tpu_custom_call") == 2


def test_the_cut_sort_carries_no_row_index(one_chip):
    """ISSUE 38: asked for a STABLE sort of one operand, the v5e compiler
    sorts a second one beside it, an ``s32[n, F]`` row index from an
    ``iota`` (a third of the sort's bytes, one more matrix of
    temporaries).  ``local_summary`` sorts the keys alone: one ``sort(``
    of ONE operand, no such iota, and the sorted copy the only matrix
    among the temporaries (the stable sort holds two)."""
    from dmlc_core_tpu.ops.quantile import local_summary

    n, F = 1 << 20, 28
    compiled = _compiled_for_the_chip(
        lambda x: local_summary(x, None, 2048),
        jax.ShapeDtypeStruct((n, F), jnp.float32, sharding=one_chip))
    lines = compiled.as_text().splitlines()
    sorts = [line for line in lines if " sort(" in line]
    assert len(sorts) == 1, sorts
    result, operands = sorts[0].split(" sort(", 1)
    assert result.split("= ", 1)[1].startswith(f"f32[{n},{F}]"), sorts[0]
    assert "," not in operands.split(")", 1)[0], sorts[0]
    assert "is_stable=true" not in sorts[0]
    assert "dmlc.cuts" in sorts[0]
    assert not [line for line in lines
                if " iota(" in line and f"s32[{n},{F}]" in line]
    # rows on the lanes, the 28 features padded to 32 sublanes
    matrix = n * 32 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 1.25 * matrix


def test_a_mesh_sorts_the_cut_columns_between_its_chips(four_chips):
    """ISSUE 52: on a mesh the cut sort is split by FEATURE COLUMNS —
    one ``all-to-all`` turns a chip's ``[S, 28]`` row shard into 7
    columns of ALL rows, which it sorts keys alone; no chip is given, or
    makes, the whole matrix: the arguments are a row shard, the
    temporaries a column shard and the sort's copy of it."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from dmlc_core_tpu.ops.quantile import _mesh_summary_fn

    n, F, ndev = 4_000_000, 28, 4
    compiled = _compiled_for_the_chip(
        _mesh_summary_fn(four_chips, n - 3, 2048, False, False),
        jax.ShapeDtypeStruct((n, F), jnp.float32, sharding=NamedSharding(
            four_chips, P("data", None))))
    lines = compiled.as_text().splitlines()
    assert len([line for line in lines if " all-to-all(" in line]) == 1
    assert [line for line in lines if " all-gather(" in line]
    (sort,) = [line for line in lines if " sort(" in line]
    result, operands = sort.split(" sort(", 1)
    assert result.split("= ", 1)[1].startswith(f"f32[{n - 3},{F // ndev}]")
    assert "," not in operands.split(")", 1)[0], sort
    assert "dmlc.cuts" in sort
    # rows on the lanes: 28 columns lie in 32 sublanes, 7 in 8
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 1.01 * n // ndev * 32 * 4
    assert mem.temp_size_in_bytes < 2.1 * n * 8 * 4
    assert mem.output_size_in_bytes < 1 << 19


@pytest.mark.parametrize("n, F", [(1_183_747, 968), (40_000_000, 28)])
def test_the_nan_scan_is_one_read_and_writes_nothing(n, F, one_chip):
    """ISSUE 50: the scan that settles the missing mode runs beside the
    matrix the cut sort is about to read — at Bosch's shape 4.27 GiB of
    15.75, with the sort's 8.79 still to come — so it may hold nothing
    of the matrix's size; and both its facts come from ONE fusion, one
    read of the matrix (two fusions took twice the time on the chip)."""
    from dmlc_core_tpu.ops.quantile import nan_scan

    compiled = _compiled_for_the_chip(
        nan_scan, jax.ShapeDtypeStruct((n, F), jnp.float32,
                                       sharding=one_chip))
    text = compiled.as_text()
    assert "dmlc.cuts.nan_scan" in text
    entry = text[text.index("ENTRY "):]
    readers = [line for line in entry.splitlines()
               if " fusion(" in line and "%x" in line.split(" fusion(")[1]]
    assert len(readers) == 1, readers
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20
    assert mem.output_size_in_bytes < 1 << 16
