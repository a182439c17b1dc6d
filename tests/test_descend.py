"""The gather-free tree descent of predict (`_predict_trees`, `_leaf_indices`)
against the gather descent it replaced, kept here as the plain reference:
same margins and leaves, bit for bit, on every path; no gather in the
programs; memory bounded in n; a row's answer independent of the blocks.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlc_core_tpu.models import histgbt as G
from dmlc_core_tpu.models.histgbt import HistGBT


# -- the reference: the descent as it was before PR 30 (a gather a level) --
def _gather_step(bins, feat, thr, dirv, node, miss_bin):
    f = feat[node]
    t = thr[node]
    row_bin = jnp.take_along_axis(bins, f[:, None], axis=1)[:, 0]
    go_right = row_bin > t
    if dirv is not None:
        d = dirv[node]
        go_right = jnp.where(row_bin == miss_bin, d == 0, go_right)
    return 2 * node + go_right.astype(jnp.int32)


def _gather_nodes(bins, tree, depth, miss_bin):
    feat, thr, dirv = tree
    node = jnp.zeros(bins.shape[0], jnp.int32)
    for level in range(depth):
        node = _gather_step(bins, feat[level], thr[level],
                            None if dirv is None else dirv[level], node,
                            miss_bin)
    return node


@partial(jax.jit, static_argnums=(4, 7))
def _gather_predict(bins, feats, thrs, leaves, depth, init, dirs, miss_bin):
    def one_tree(carry, tree):
        *split, leaf = tree
        return carry + leaf[_gather_nodes(bins, split, depth, miss_bin)], None

    return jax.lax.scan(one_tree, init, (feats, thrs, dirs, leaves))[0]


@partial(jax.jit, static_argnums=(3, 5))
def _gather_leaves(bins, feats, thrs, depth, dirs, miss_bin):
    def one_tree(_, tree):
        return 0, _gather_nodes(bins, tree, depth, miss_bin)

    return jax.lax.scan(one_tree, 0, (feats, thrs, dirs))[1].T


# -- cases --------------------------------------------------------------
_N_BINS = 256


def _forest(rng, T, depth, F, missing=False, zero_from=None,
            degenerate=0.1):
    """Random depth-complete trees in the layout fit() writes: tables
    [T, depth, half] whose level l uses its first 2^l entries (zeros
    past them), leaves [T, 2^depth]."""
    half = 1 << (depth - 1)
    feats = rng.integers(0, F, (T, depth, half)).astype(np.int32)
    thrs = rng.integers(0, _N_BINS - 1, (T, depth, half)).astype(np.int32)
    # degenerate splits (no profitable split: every row goes left)
    thrs[rng.random(thrs.shape) < degenerate] = _N_BINS - 1
    dirs = (rng.integers(0, 2, (T, depth, half)).astype(np.int32)
            if missing else None)
    leaves = rng.normal(size=(T, 1 << depth)).astype(np.float32)
    for table in (feats, thrs) + ((dirs,) if missing else ()):
        for level in range(depth):
            table[:, level, 1 << level:] = 0
        if zero_from is not None:        # a zero-padded chunk
            table[zero_from:] = 0
    if zero_from is not None:
        leaves[zero_from:] = 0
    return feats, thrs, leaves, dirs


def _case(depth, T, F, n, init=True, missing=False, zero_from=None,
          degenerate=0.1, bins_dtype=np.uint8, seed=0):
    return dict(depth=depth, T=T, F=F, n=n, init=init, missing=missing,
                zero_from=zero_from, degenerate=degenerate,
                bins_dtype=bins_dtype, seed=seed)


_CASES = {
    "depth1-T1-F4-n1": _case(1, 1, 4, 1),
    "depth1-T25-F28-n37": _case(1, 25, 28, 37),
    "depth3-T1-F40-n37": _case(3, 1, 40, 37),
    "depth3-T25-F4-n16384": _case(3, 25, 4, 16_384),
    "depth3-T64-F28-n1": _case(3, 64, 28, 1),
    "depth6-T64-F28-n16384": _case(6, 64, 28, 16_384),
    "depth6-T25-F40-n37": _case(6, 25, 40, 37),
    "depth6-T1-F28-n16384": _case(6, 1, 28, 16_384),
    "depth8-T64-F4-n37": _case(8, 64, 4, 37),
    "depth8-T25-F40-n1000": _case(8, 25, 40, 1000),
    # 64 trees walk 16,384 rows a block: three blocks, the last part full
    "several-row-blocks": _case(3, 64, 28, 40_037),
    # 100 trees are two blocks of 50; 65 are two of 33, one all-zero pad
    "several-tree-blocks": _case(3, 100, 4, 23_000),
    "padded-tree-block": _case(3, 65, 4, 37),
    "no-init": _case(6, 25, 28, 37, init=False),
    "missing": _case(6, 64, 28, 5000, missing=True),
    "missing-depth1": _case(1, 25, 4, 37, missing=True),
    "missing-several-blocks": _case(3, 64, 4, 40_037, missing=True),
    "zero-padded-chunk": _case(6, 64, 28, 1000, zero_from=36),
    "zero-padded-chunk-missing": _case(3, 64, 4, 37, missing=True,
                                       zero_from=1),
    "degenerate-thr": _case(6, 25, 28, 1000, degenerate=1.0),
    "int32-bins": _case(3, 25, 28, 37, bins_dtype=np.int32),
}


def _inputs(depth, T, F, n, init, missing, zero_from, degenerate,
            bins_dtype, seed):
    rng = np.random.default_rng(seed)
    feats, thrs, leaves, dirs = _forest(rng, T, depth, F, missing,
                                        zero_from, degenerate)
    bins = rng.integers(0, _N_BINS - 1, (n, F)).astype(bins_dtype)
    miss_bin = -1
    if missing:                      # a fifth of the values are missing
        miss_bin = _N_BINS - 1
        bins[rng.random(bins.shape) < 0.2] = miss_bin
    margin = (rng.normal(size=n).astype(np.float32) if init else None)
    return bins, feats, thrs, leaves, dirs, miss_bin, margin


@pytest.mark.parametrize("program", ["_predict_trees", "_leaf_indices"])
@pytest.mark.parametrize("case", list(_CASES))
def test_descent_equals_the_gather_descent(case, program):
    c = _CASES[case]
    bins, feats, thrs, leaves, dirs, miss_bin, margin = _inputs(**c)
    depth = c["depth"]
    if program == "_leaf_indices":
        got = G._leaf_indices(bins, feats, thrs, depth, dirs, miss_bin)
        want = _gather_leaves(bins, feats, thrs, depth, dirs, miss_bin)
        assert got.shape == (c["n"], c["T"]) and got.dtype == jnp.int32
    else:
        base = 0.5
        got = G._predict_trees(bins, feats, thrs, leaves, depth, base,
                               margin, dirs, miss_bin)
        start = (jnp.full(c["n"], base, jnp.float32) if margin is None
                 else margin)
        want = _gather_predict(bins, feats, thrs, leaves, depth, start,
                               dirs, miss_bin)
        assert got.shape == (c["n"],) and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("missing", [False, True],
                         ids=["plain", "missing"])
def test_apply_trees_multiclass_equals_the_gather_descent(missing):
    """`_apply_trees` on a [T, K, depth, half] forest of 70 trees: two
    chunks of `_TREE_CHUNK`, the second zero-padded, margins class-major
    [K, n] (as the device holds them) threaded through both."""
    depth, T, K, F, n = 3, 70, 3, 28, 1000
    rng = np.random.default_rng(3)
    per_class = [_forest(rng, T, depth, F, missing) for _ in range(K)]
    keys = ("feat", "thr", "leaf") + (("dir",) if missing else ())
    trees = [{k: np.stack([per_class[c][i][t] for c in range(K)])
              for i, k in enumerate(keys)} for t in range(T)]
    bins = rng.integers(0, _N_BINS - 1, (n, F)).astype(np.uint8)
    miss_bin = -1
    model = HistGBT(max_depth=depth, num_class=K, objective="multi:softmax")
    if missing:
        miss_bin = _N_BINS - 1
        bins[rng.random(bins.shape) < 0.2] = miss_bin
        model._missing = True
        model.cuts = jnp.zeros((F, _N_BINS - 2))
        assert model._miss_bin() == miss_bin
    init = rng.normal(size=(n, K)).astype(np.float32)
    stacked = model._stacked_trees(trees)
    assert [c["feat"].shape for c in stacked] == [
        (G._TREE_CHUNK, K, depth, 1 << (depth - 1))] * 2
    got = model._apply_trees(jnp.asarray(bins), stacked,
                             jnp.asarray(init.T)).T
    want = np.stack(
        [np.asarray(_gather_predict(
            bins, *per_class[c][:3], depth, init[:, c], per_class[c][3],
            miss_bin)) for c in range(K)], axis=1)
    np.testing.assert_array_equal(np.asarray(got), want)


_SHAPES = dict(n=16_384, T=64, depth=6, F=28)


def _lowered(program, n, T, depth, F, dirs=True):
    S = jax.ShapeDtypeStruct
    table = S((T, depth, 1 << (depth - 1)), jnp.int32)
    dirs = table if dirs else None
    if program == "_leaf_indices":
        return G._leaf_indices.lower(S((n, F), jnp.uint8), table, table,
                                     depth, dirs, 255)
    return G._predict_trees.lower(
        S((n, F), jnp.uint8), table, table, S((T, 1 << depth), jnp.float32),
        depth, 0.0, S((n,), jnp.float32), dirs, 255)


@pytest.mark.parametrize("program", ["_predict_trees", "_leaf_indices"])
def test_programs_contain_no_gather(program):
    """Neither as traced nor as compiled: a TPU runs a gather one element
    at a time (the descent was 161.5 of a scoring call's 167 ms)."""
    lowered = _lowered(program, **_SHAPES)
    assert "gather" not in lowered.as_text()
    assert "gather" not in lowered.compile().as_text()


def test_predict_trees_memory_is_bounded_in_n():
    """A `_PREDICT_BATCH` of rows walks in blocks: the [rows, trees x
    nodes] intermediates of one dense pass would be 16 GB."""
    compiled = _lowered("_predict_trees", n=HistGBT._PREDICT_BATCH, T=64,
                        depth=6, F=28, dirs=False).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("n,T,F,want", [
    (16_384, 64, 28, (1, 16_384, 8)),      # a chunk of the scoring cell
    (16_384, 128, 28, (1, 16_384, 16)),    # its slab program: both chunks
    (1, 1, 28, (1, 1024, 1)),
    (2_000_000, 64, 28, (123, 16_384, 8)),
    (16_384, 25, 28, (1, 16_384, 4)),      # fit(eval_set=...)'s chunk
    (100_000, 25, 2000, (20, 5120, 1)),    # wide rows: more trees a block
    (100_000, 64, 2000, (20, 5120, 3)),
    (65_536, 64, 256, (4, 16_384, 8)),
    (40_000, 100, 28, (3, 16_384, 13)),    # predict_leaf: exact tree count
])
def test_descend_blocks_follow_the_shapes(n, T, F, want):
    row_blocks, rows, tree_blocks = G._descend_blocks(n, T, F)
    assert (row_blocks, rows, tree_blocks) == want
    assert row_blocks * rows >= n and rows % G._ROW_TILE == 0
    trees = -(-T // tree_blocks)
    assert trees <= G._TREE_BLOCK[1]
    assert rows * trees <= max(G._DESCEND_BLOCK, G._ROW_TILE * trees)


@pytest.fixture(scope="module")
def fitted():
    rng = np.random.default_rng(5)
    X = rng.normal(size=(2000, 6)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
    return HistGBT(n_trees=5, max_depth=3, n_bins=32).fit(X, y)


@pytest.mark.parametrize("output", ["predict", "margin", "predict_leaf"])
def test_predict_equals_predict_of_its_halves(fitted, output):
    """Block-size independence: 20,000 rows walk as two strided blocks of
    16,384, each half as one block."""
    assert G._descend_blocks(20_000, G._TREE_CHUNK, 6)[0] == 2
    assert G._descend_blocks(10_000, G._TREE_CHUNK, 6)[0] == 1
    X = np.random.default_rng(6).normal(size=(20_000, 6)).astype(np.float32)
    call = {"predict": fitted.predict,
            "margin": partial(fitted.predict, output_margin=True),
            "predict_leaf": fitted.predict_leaf}[output]
    np.testing.assert_array_equal(
        call(X), np.concatenate([call(X[:10_000]), call(X[10_000:])]))
