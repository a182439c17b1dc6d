"""ISSUE 51: the sparse one-hot table fed from pages, on the CPU at toy
size (6,000 rows x 302 columns of ``benchmark/datagen_onehot``'s rule):
``DiskRowIter`` pages -> ``iter_dense_slabs`` -> ``make_device_data_iter``
(the streaming sketch, then slab binning) -> ``fit_device`` at depth 8.

(a) the paged handle is ``make_device_data``'s of the densified matrix,
byte for byte, under the same cuts; (b) the sketched cuts keep the
documented rank error on the numeric columns and a cut between 0 and 1
of every indicator, down to a level ONE row holds; (c) pages replay the
seeded blocks byte for byte across page boundaries that split a slab,
and a LibSVM text of the same rows parses to the same pages; (d) the
trees grown on the paged handle at depth 8 are the reference's (rounds 0
and 1); (e) every span of the iterator path opens once per page, slab or
pass, with its counters; and the two gates the table forced: a slab of
2^32 bytes is refused, and every histogram call of a build cut on
features states its own scoped-VMEM limit.
"""

import jax
import numpy as np
import pytest

from benchmark import (checks_paged, datagen_onehot as D,
                       reference_paged as refp, system_paged)
from dmlc_core_tpu.base.logging import Error
from dmlc_core_tpu.data.iter import (DiskRowIter, RowBlockIter,
                                     iter_dense_slabs)
from dmlc_core_tpu.data.row_block import RowBlock
from dmlc_core_tpu.models import HistGBT
from dmlc_core_tpu.ops import histogram as H
from dmlc_core_tpu.ops.quantile import SketchAccumulator
from dmlc_core_tpu.utils import profiler

from test_hist_nested_blocks_chip import _limits

SEED = 2**31 + 51
ROWS, SLAB = 6000, 1024
#: nineteen fields as the table has them, two of them wide: 290 levels
LEVELS = (8, 90, 140, 6, 3, 4, 3, 3, 3, 3, 3, 3, 3, 2, 3, 3, 3, 4, 3)
F = D.NUMERIC + sum(LEVELS)
#: a page a block of 1,500 rows: its boundaries fall inside slabs of 1,024
PAGE_BYTES = 500 << 10
CFG = {"learning_rate": 0.1, "reg_lambda": 1.0, "min_child_weight": 1.0,
       "n_bins": 64, "base_score": 0.0, "max_depth": 8}


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    """The seeded blocks in pieces of 1,500 rows, their page cache, and
    the dense matrix no ingest below is handed."""
    whole = list(D.allstate_like(ROWS, SEED, levels=LEVELS))
    blocks = []
    for offset, index, value, y in whole:
        for lo in range(0, len(y), 1500):
            hi = min(lo + 1500, len(y))
            a, b = offset[lo], offset[hi]
            blocks.append((offset[lo:hi + 1] - a, index[a:b], value[a:b],
                           y[lo:hi]))
    path = str(tmp_path_factory.mktemp("pages") / "rows.cache")
    pages = DiskRowIter(system_paged.CsrBlocks(blocks), path, page_bytes=PAGE_BYTES)
    X = checks_paged.dense_rows(blocks, 0, ROWS, F).astype(np.float32)
    y = np.concatenate([b[3] for b in blocks])
    yield blocks, pages, X, y
    pages.close()


def _model(**kw):
    return HistGBT(n_trees=2, max_depth=CFG["max_depth"],
                   n_bins=CFG["n_bins"],
                   learning_rate=CFG["learning_rate"],
                   objective="binary:logistic", **kw)


@pytest.fixture(scope="module")
def paged(table):
    _blocks, pages, _X, _y = table
    model = _model(hist_method="pallas")
    handle = model.make_device_data_iter(
        lambda: iter_dense_slabs(pages, F, SLAB))
    return model, handle


# -- (c) the pages -----------------------------------------------------------------

def _csr(blocks):
    """Blocks joined: per-row lengths, indices, values, labels."""
    return (np.concatenate([np.diff(b.offset) for b in blocks]),
            np.concatenate([b.index for b in blocks]),
            np.concatenate([b.value for b in blocks]),
            np.concatenate([b.label for b in blocks]))


def test_pages_replay_the_blocks_across_boundaries_that_split_a_slab(table):
    blocks, pages, X, y = table
    got = list(pages)
    assert len(got) == pages.num_pages >= 3
    sizes = np.cumsum([p.size for p in got])
    assert sizes[-1] == ROWS == pages.num_rows and pages.num_col <= F
    assert any(s % SLAB for s in sizes[:-1])       # a page ends inside a slab
    want = _csr([RowBlock(offset=o, label=l, index=i, value=v)
                 for o, i, v, l in blocks])
    for a, b in zip(_csr(got), want):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    # a second replay is the first; the slabs are the dense rows
    for a, b in zip(_csr(list(pages)), want):
        assert np.array_equal(a, b)
    slabs = [(np.array(Xs), np.array(ys), np.array(ws))
             for Xs, ys, ws in iter_dense_slabs(pages, F, SLAB)]
    assert [len(s[0]) for s in slabs] == [SLAB] * 5 + [ROWS - 5 * SLAB]
    assert np.array_equal(np.concatenate([s[0] for s in slabs]), X)
    assert np.array_equal(np.concatenate([s[1] for s in slabs]), y)
    assert all((s[2] == 1.0).all() for s in slabs)


def test_a_libsvm_text_of_the_rows_parses_to_the_same_pages(table, tmp_path):
    blocks, pages, _X, _y = table
    text = tmp_path / "rows.libsvm"
    with open(text, "w") as f:
        for offset, index, value, y in blocks:
            for r in range(len(y)):
                a, b = offset[r], offset[r + 1]
                f.write(f"{int(y[r])} " + " ".join(
                    f"{i}:{v!r}" for i, v in zip(index[a:b].tolist(),
                                                 value[a:b].tolist())) + "\n")
    parsed = RowBlockIter.create(f"{text}#{tmp_path / 'text.cache'}")
    try:
        for a, b in zip(_csr(list(parsed)), _csr(list(pages))):
            assert np.array_equal(a, b)
    finally:
        parsed.close()


# -- (a) the handle ------------------------------------------------------------------

def test_the_paged_handle_is_the_dense_handle_under_the_same_cuts(table,
                                                                  paged):
    _blocks, _pages, X, y = table
    model, handle = paged
    dense = _model()
    want = dense.make_device_data(X, y, cuts=model.cuts)
    assert (handle["n"], handle["n_padded"], handle["n_features"]) == \
        (want["n"], want["n_padded"], want["n_features"]) == (ROWS, ROWS, F)
    for key in ("bins_t", "y_d", "w_d"):
        a, b = np.asarray(handle[key]), np.asarray(want[key])
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), key
    # ... which are the reference's bins of the densified rows: an
    # absent entry bins as 0.0
    assert checks_paged.bins_mismatches(
        X.astype(np.float64), np.asarray(handle["bins_t"]),
        np.asarray(model.cuts)) == 0


# -- (b) the sketched cuts ---------------------------------------------------------------

def test_sketched_cuts_keep_the_rank_bound_and_every_indicator(table, paged):
    blocks, _pages, X, _y = table
    model, _handle = paged
    cuts = np.asarray(model.cuts)
    ids = list(range(D.NUMERIC))
    got = checks_paged.cut_numbers(
        checks_paged.numeric_columns(blocks, ids), ids,
        checks_paged.occupied_indicators(blocks, ROWS, F), cuts)
    slabs = -(-ROWS // SLAB)
    assert got["cuts_rank_error"] <= refp.sketch_eps(8 * CFG["n_bins"],
                                                     slabs)
    assert got["indicator_cuts_missing"] == 0
    # down to a level ONE row holds, and a level all rows but a few hold
    count = (X[:, D.NUMERIC:] == 1.0).sum(axis=0)
    assert (count == 1).any() and (count > ROWS // 2).any()
    assert len(checks_paged.occupied_indicators(blocks, ROWS, F)) == \
        np.count_nonzero(count)
    # the controls: cuts from the first slab alone miss the bound on
    # some numeric column, cuts pushed past 1.0 lose the indicator
    first = SketchAccumulator(F, n_summary=8 * CFG["n_bins"])
    first.add(X[:256])
    assert checks_paged.cut_numbers(
        checks_paged.numeric_columns(blocks, ids), ids, [],
        np.asarray(first.finalize(CFG["n_bins"])))["cuts_rank_error"] > \
        refp.sketch_eps(8 * CFG["n_bins"], slabs)
    lost = cuts.copy()
    lost[F - 1] = 2.0 + np.arange(cuts.shape[1])
    assert refp.unsplit_indicators(lost, [F - 2, F - 1]) == 1


@pytest.mark.parametrize("ones", [1, 5, 2995, 5999])
def test_a_two_valued_column_keeps_its_cut_whatever_its_share(ones):
    """0/1 with ``ones`` rows set, streamed in six pages through the
    ladder: the strictly increasing guard of the cuts leaves a cut
    between the two values however few rows hold either."""
    rng = np.random.default_rng(ones)
    x = np.zeros((6000, 2), np.float32)
    x[rng.choice(6000, ones, replace=False), 0] = 1.0
    x[:, 1] = rng.normal(size=6000)
    sk = SketchAccumulator(2, n_summary=512, buffer_pages=2)
    for lo in range(0, 6000, 1000):
        sk.add(x[lo:lo + 1000], np.ones(1000, np.float32))
    cuts = np.asarray(sk.finalize(64))
    assert refp.unsplit_indicators(cuts, [0]) == 0
    assert np.all(np.diff(cuts, axis=1) > 0)


def test_uniform_weights_take_the_unweighted_summary():
    """``iter_dense_slabs`` hands 1.0 a row where a page has no weights:
    the sketch then sorts keys alone, and its cuts are those of no
    weights at all; unequal weights keep the weighted path."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(4000, 3)).astype(np.float32)

    def cuts(w):
        sk = SketchAccumulator(3, n_summary=256)
        for lo in range(0, 4000, 1000):
            sk.add(x[lo:lo + 1000], None if w is None else w[lo:lo + 1000])
        return np.asarray(sk.finalize(32))

    assert np.array_equal(cuts(np.ones(4000, np.float32)), cuts(None))
    assert np.array_equal(cuts(np.full(4000, 2.5, np.float32)), cuts(None))
    skew = np.where(x[:, 0] > 0, 4.0, 1.0).astype(np.float32)
    assert not np.array_equal(cuts(skew), cuts(None))


# -- (d) the trees -------------------------------------------------------------------

def test_depth_8_trees_on_the_paged_handle_are_the_references(table, paged):
    _blocks, _pages, _X, y = table
    model, handle = paged
    model.fit_device(handle)
    assert model.round_plan["hist_method"] == ["pallas"] * 8
    trees = [{k: np.asarray(v) for k, v in t.items()} for t in model.trees]
    assert trees[0]["feat"].shape == (8, 128) and len(trees[0]["leaf"]) == 256
    bins_t = np.asarray(handle["bins_t"])[:, :ROWS]
    got = checks_paged.boost_tree_numbers(bins_t, y, trees, CFG)
    # the root's (feature, threshold) reaches the reference's best gain;
    # leaves to the deep cell's tolerance (benchmark/tests/test_deep.py:
    # the boost mixes' 1e-5 on tree 0, tree 1 after bfloat16 gradients)
    assert got["tree0.root_gain_gap"] <= 1e-6, got
    assert got["tree0.reported_gain_gap"] <= 1e-2, got     # float32 gain
    assert got["tree0.leaf_gap"] <= 1e-5, got
    assert got["tree1.leaf_gap_by_rows"] <= 1e-2, got
    # a second fit of the handle: the same bytes
    again = _model(hist_method="pallas")
    again.cuts = model.cuts
    again.fit_device(handle)
    for a, b in zip(model.trees, again.trees):
        for k in a:
            assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k


# -- (e) spans and counters ------------------------------------------------------------

def test_every_span_of_the_iterator_path_opens_with_its_counters(table):
    _blocks, pages, _X, _y = table
    model = _model()
    handle = model.make_device_data_iter(
        lambda: iter_dense_slabs(pages, F, SLAB))
    jax.block_until_ready(handle["bins_t"])
    rec = [r for r in profiler.op_log() if r["name"] == "dmlc.ingest"][-1]
    slabs, n_pages = -(-ROWS // SLAB), pages.num_pages
    nnz = sum(p.nnz for p in pages)
    assert rec["counts"] == {"rows": ROWS, "features": F, "slabs": slabs,
                             "dense_bytes": ROWS * F * 4,
                             "pages": 2 * n_pages, "nnz": 2 * nnz}
    kids = {k.removeprefix("dmlc.ingest."): v
            for k, v in rec["children"].items()}
    for name in ("iter.sketch_pass", "iter.bin_pass", "iter.sketch_finalize",
                 "stream", "concat"):
        assert kids[name][0] == 1, name
    # a wait a page and one for the end of each replay; a copy a slab a
    # pass; the scan and the sketch's add a slab of the first pass
    assert kids["iter.page_wait"][0] == 2 * (n_pages + 1)
    assert kids["iter.copy"][0] == 2 * slabs
    assert kids["iter.copy"][3] == 2 * ROWS * F * 4
    assert kids["iter.nan_scan"][0] == kids["iter.sketch_add"][0] == slabs
    # a densify per piece of a page inside a slab: every page, and one
    # more for each page boundary inside a slab; all rows, both passes
    assert kids["iter.densify"][0] >= 2 * n_pages
    assert kids["iter.densify"][3] == 2 * ROWS * F * 4
    # (a put and a dispatch a slab and chip that holds rows of it)
    assert kids["put"][0] == kids["bin_dispatch"][0] >= slabs
    # the passes hold the host's time; the sketch's device work runs
    # under its own scope
    assert kids["iter.sketch_pass"][1] >= kids["iter.sketch_add"][1]
    text = SketchAccumulator.add.__globals__["_page_summary"].lower(
        np.zeros((64, 4), np.float32), None, 16, False).as_text(
            debug_info=True)
    assert "dmlc.sketch.add" in text and "dmlc.cuts" not in text


def test_a_page_wait_outside_an_operation_marks_nothing(table):
    _blocks, pages, _X, _y = table
    before = len(profiler.op_log())
    assert sum(p.size for p in pages) == ROWS
    assert len(profiler.op_log()) == before
    with profiler.span("dmlc.predict") as sp:
        profiler.count_in_op(pages=2)
        profiler.count_in_op(pages=3, nnz=7)
    assert (sp.counts["pages"], sp.counts["nnz"]) == (5, 7)
    profiler.count_in_op(pages=1)                  # no operation: nothing


# -- the two gates ----------------------------------------------------------------------

def test_a_slab_of_2_to_the_32_bytes_is_refused_with_the_remedy():
    with pytest.raises(Error, match=r"batch_rows <= 254019"):
        iter_dense_slabs([], 4227, 254_020)
    assert ((1 << 32) - 1) // (4227 * 4) == 254_019


def test_every_call_of_a_feature_blocked_build_states_its_limit():
    """At the table's own width and 256 bins a root build is eleven
    kernel calls (feature blocks of 392 rows, the rest 307) and a build
    of 32 nodes twenty-two (200): each states ``_NESTED_BLOCKS_VMEM`` —
    the parent left the 8 builds of level 4 to the default scope and the
    chip's compiler refused them at 18.75 MiB.  A build of one feature
    block (HIGGS's 28 columns) states nothing, as before."""
    rng = np.random.default_rng(0)
    n, n_bins = 300, 256

    def limits(features, n_nodes):
        args = (jax.numpy.asarray(
                    rng.integers(0, 2, (features, n)).astype(np.uint8)),
                jax.numpy.asarray(
                    rng.integers(0, n_nodes, n).astype(np.int32)),
                jax.numpy.ones(n, np.float32), jax.numpy.ones(n, np.float32))
        return _limits(jax.make_jaxpr(lambda *a: H.build_histogram(
            *a, n_nodes, n_bins, "pallas", transposed=True))(*args).jaxpr)

    assert H.hist_feature_blocks(n_bins, 4227, 8) == (392,) * 10 + (307,)
    assert limits(4227, 8) == [H._NESTED_BLOCKS_VMEM] * 11
    assert limits(4227, 32) == [H._NESTED_BLOCKS_VMEM] * 22
    assert limits(28, 32) == [None]
    assert H._SCOPED_VMEM < H._NESTED_BLOCKS_VMEM < H._STACKED_VMEM
