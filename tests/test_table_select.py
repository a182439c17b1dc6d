"""The gather-free table lookup of the round program (ISSUE 46).

``table_select(table, node, n_entries)`` reads a tiny per-node table for
every row as the sum, over pieces of ``ROW_MAJOR_MAX`` (64) entries, of
the ``[n, N]`` compare-and-sum the round-program builder used to write.

* the hoisted function returns ``table[node]`` bit for bit at every
  size, one piece or many: 0 for a padding row's -1 and for a
  node past the table, nothing of a NaN or inf no row selects, ``+0.0``
  for a selected ``-0.0`` — what ONE compare-and-sum returns;
* a depth-8 and a depth-9 fit (the tail's tables of 128 to 512 entries,
  at depth 9 ``route``'s of 128 and 256 too), with and without missing
  values, and a loss-guide fit at depth 6 (a table of 128) grow the
  trees and margins of a fit whose every lookup is plain indexing.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dmlc_core_tpu.models import HistGBT  # noqa: E402
from dmlc_core_tpu.models import histgbt as hg  # noqa: E402
from dmlc_core_tpu.ops import table_select as ts  # noqa: E402
from dmlc_core_tpu.parallel.mesh import local_mesh  # noqa: E402


def _plain(table, node, n_entries):
    """Plain indexing: numpy's, on the host.  (A ``table[node]`` traced
    into the program would do for the integers; XLA:CPU contracts the
    gathered ``leaf_w * eta`` and the margin's add into one fma, and the
    margins then differ in the last place for no lookup's fault.)"""
    def index(table, node):
        inside = (node >= 0) & (node < n_entries)
        got = table[np.clip(node, 0, n_entries - 1)]
        return np.where(inside, got, 0).astype(table.dtype)

    return jax.pure_callback(
        index, jax.ShapeDtypeStruct(node.shape, table.dtype), table, node)


def _bits(x):
    return np.asarray(x).view(np.uint32)


def test_a_piece_is_the_last_size_the_rows_stay_on_the_lanes():
    assert ts.ROW_MAJOR_MAX == 64


@pytest.mark.parametrize("dtype", ["int32", "float32"])
@pytest.mark.parametrize("n_entries", [1, 2, 32, 64, 65, 128, 256, 512])
def test_the_lookup_is_plain_indexing_bit_for_bit(n_entries, dtype):
    rng = np.random.default_rng(n_entries)
    n = 4099
    node = rng.integers(0, n_entries, n).astype(np.int32)
    node[rng.integers(0, n, 41)] = -1                  # padding rows
    node[rng.integers(0, n, 7)] = n_entries            # past the table
    if dtype == "float32":
        table = rng.normal(size=n_entries).astype(np.float32)
    else:
        table = rng.integers(-2**31, 2**31, n_entries).astype(np.int32)
    negzero = None
    if n_entries >= 2:
        # an entry no row selects may hold anything
        unselected = int(rng.integers(0, n_entries))
        node[node == unselected] = (unselected + 1) % n_entries
        if dtype == "float32":
            table[unselected] = np.nan if n_entries % 3 else np.inf
    if n_entries >= 32 and dtype == "float32":
        negzero = (unselected + 2) % n_entries
        table[negzero] = -0.0
        node[:5] = negzero
    inside = (node >= 0) & (node < n_entries)
    want = np.where(inside, table[np.clip(node, 0, n_entries - 1)], 0)
    want = want.astype(table.dtype)
    if negzero is not None:
        assert np.signbit(want[:5]).all()
        want = want + 0                                # -0.0 reads +0.0

    got = jax.jit(ts.table_select, static_argnums=2)(
        jnp.asarray(table), jnp.asarray(node), n_entries)
    assert got.dtype == table.dtype and got.shape == (n,)
    assert np.array_equal(_bits(got), _bits(want))
    if n_entries >= 2:
        # and it is what ONE compare-and-sum over the whole table
        # returns, however many pieces it went in
        old = jax.jit(ts._row_major, static_argnums=2)(
            jnp.asarray(table), jnp.asarray(node), n_entries)
        assert np.array_equal(_bits(got), _bits(old))


def test_a_selected_nan_or_inf_comes_back_as_it_is():
    table = np.array([np.nan, np.inf, -np.inf, 1.5] * 32, np.float32)
    node = np.arange(-1, 128, dtype=np.int32)
    got = np.asarray(ts.table_select(jnp.asarray(table), jnp.asarray(node),
                                     128))
    assert got[0] == 0
    assert np.array_equal(_bits(got[1:]), _bits(table))


# ----------------------------------------------------------------------
# the round program
# ----------------------------------------------------------------------

def _data(n=3000, F=6, seed=5, nan_share=0.0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    y = ((X[:, 0] * X[:, 1] + np.sin(3 * X[:, 2]) + 0.3 * X[:, 3]
          + 0.5 * rng.normal(size=n)) > 0).astype(np.float32)
    if nan_share:
        X[rng.random(X.shape) < nan_share] = np.nan
    return X, y


@pytest.fixture(autouse=True)
def _no_program_outlives_its_lookups():
    """The round programs are cached by plan, and the plan does not know
    a test swapped the lookup: none is kept from one fit to the next."""
    yield
    hg._ROUND_FN_CACHE.clear()
    hg._AOT_EXEC_CACHE.clear()


def _fit(depth, X, y):
    hg._ROUND_FN_CACHE.clear()
    hg._AOT_EXEC_CACHE.clear()
    m = HistGBT(mesh=local_mesh(1), n_trees=3, max_depth=depth, n_bins=32,
                learning_rate=0.1)
    m.fit(X, y)
    return m


def _same_fit(a, b, keys):
    assert len(a.trees) == len(b.trees) == 3
    for ta, tb in zip(a.trees, b.trees):
        assert sorted(ta) == sorted(tb) == sorted(keys)
        for k in keys:
            xa, xb = np.asarray(ta[k]), np.asarray(tb[k])
            assert xa.dtype == xb.dtype and xa.shape == xb.shape
            assert np.array_equal(xa.view(np.uint32), xb.view(np.uint32)), k
    assert np.array_equal(_bits(a.train_margins()), _bits(b.train_margins()))


def _plain_lookups(monkeypatch):
    """Every lookup of the round program, at any size, as plain indexing."""
    seen = []

    def plain(table, node, n_entries):
        seen.append(n_entries)
        return _plain(table, node, n_entries)

    monkeypatch.setattr(hg, "table_select", plain)
    return seen


@pytest.mark.parametrize("depth, nan_share",
                         [(8, 0.0), (8, 0.3), (9, 0.0), (9, 0.3)])
def test_a_deep_fit_grows_the_trees_of_plain_indexing(depth, nan_share,
                                                      monkeypatch):
    X, y = _data(nan_share=nan_share)
    sizes = []

    def counted(table, node, n_entries):
        sizes.append(n_entries)
        return ts.table_select(table, node, n_entries)

    monkeypatch.setattr(hg, "table_select", counted)
    ours = _fit(depth, X, y)
    # every lookup of the round program is the hoisted function's:
    # route's at each level below the root, the tail's, the leaf's
    tables = 3 if nan_share else 2
    want = [1 << lvl for lvl in range(depth) for _ in range(tables)]
    assert sizes == want + [1 << depth]
    seen = _plain_lookups(monkeypatch)
    plain = _fit(depth, X, y)
    assert seen == sizes
    keys = ["feat", "thr", "gain", "leaf"] + (["dir"] if nan_share else [])
    assert bool(ours.round_plan["missing"]) == bool(nan_share)
    _same_fit(ours, plain, keys)
    assert np.array_equal(_bits(ours.predict(X[:256])),
                          _bits(plain.predict(X[:256])))


def test_a_lossguide_fit_at_depth_6_looks_128_entries_up(monkeypatch):
    monkeypatch.setenv("DMLC_GROW_POLICY", "lossguide")
    X, y = _data()
    ours = _fit(6, X, y)
    assert ours.round_plan["grow_policy"] == "lossguide"
    seen = _plain_lookups(monkeypatch)
    plain = _fit(6, X, y)
    assert 128 in seen
    _same_fit(ours, plain, ["feat", "thr", "gain", "leaf"])
