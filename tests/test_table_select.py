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

``route``'s packed word (ISSUE 55): where a device's rows are many
(``CHAIN_MIN_ROWS``) a level packs feature, threshold and direction into
ONE int32 a node (``SplitWord``), looks it up once a row — as a chain of
selects (``chain_select``) up to ``CHAIN_MAX_ENTRIES`` parents, through
``table_select`` past them — and unpacks it per row.

* ``chain_select`` is plain indexing bit for bit; pack, look up, unpack
  returns every field at the benchmark's widths, and a split that does
  not fit 31 bits is refused where the plan is made;
* fits on both sides of both constants grow the same bytes, and the
  bytes of plain indexing: one class and three, one device and four;
* ``round_plan["route_lookups"]`` says what was traced, and below the
  row constant the round program's jaxpr is the parent commit's text.
"""

import hashlib
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dmlc_core_tpu.base.logging import Error  # noqa: E402
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


def _fit(depth, X, y, devices=1, **kw):
    hg._ROUND_FN_CACHE.clear()
    hg._AOT_EXEC_CACHE.clear()
    m = HistGBT(mesh=local_mesh(devices), n_trees=3, max_depth=depth,
                n_bins=32, learning_rate=0.1, **kw)
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


def test_a_lossguide_fit_at_depth_6_looks_127_entries_up(monkeypatch):
    """The 64 leaves of a depth-6 budget are a node list of 127 entries:
    the one lookup of a loss-guide tree, in two pieces."""
    X, y = _data()
    ours = _fit(6, X, y, grow_policy="lossguide")
    assert ours.round_plan["grow_policy"] == "lossguide"
    seen = _plain_lookups(monkeypatch)
    plain = _fit(6, X, y, grow_policy="lossguide")
    assert seen and set(seen) == {127}
    _same_fit(ours, plain, ["feat", "thr", "gain", "left", "right",
                            "value"])


# ----------------------------------------------------------------------
# route's ONE packed word a level (ISSUE 55)
# ----------------------------------------------------------------------

def _nodes(rng, n_entries, n=4099):
    node = rng.integers(0, n_entries, n).astype(np.int32)
    node[:n_entries] = np.arange(n_entries)            # every entry
    node[rng.integers(n_entries, n, 41)] = -1          # padding rows
    node[rng.integers(n_entries, n, 7)] = n_entries    # past the table
    return node


def _index(table, node):
    inside = (node >= 0) & (node < len(table))
    return np.where(inside, table[np.clip(node, 0, len(table) - 1)], 0)


@pytest.mark.parametrize("n_entries", [1, 2, 3, 4, 8, 16, 31, 32, 33, 64])
def test_the_chain_is_plain_indexing_bit_for_bit(n_entries):
    rng = np.random.default_rng(n_entries)
    node = _nodes(rng, n_entries)
    table = rng.integers(-2**31, 2**31, n_entries).astype(np.int32)
    got = jax.jit(ts.chain_select, static_argnums=2)(
        jnp.asarray(table), jnp.asarray(node), n_entries)
    assert got.dtype == jnp.int32 and got.shape == node.shape
    assert np.array_equal(np.asarray(got), _index(table, node))
    pieces = ts.table_select(jnp.asarray(table), jnp.asarray(node),
                             n_entries)
    assert np.array_equal(np.asarray(got), np.asarray(pieces))


@pytest.mark.parametrize("n_features, n_bins, missing, n_prev", [
    (28, 256, False, 32),          # HIGGS
    (968, 256, True, 64),          # Bosch
    (4227, 256, True, 128),        # Allstate: 13 + 8 + 1 bits
    (6, 32, True, 16),             # the fits below
    (1 << 22, 256, True, 4),       # 22 + 8 + 1: the last that fits
])
def test_pack_look_up_unpack_returns_every_field(n_features, n_bins,
                                                 missing, n_prev):
    rng = np.random.default_rng(n_features)
    feat = rng.integers(0, n_features, n_prev).astype(np.int32)
    thr = rng.integers(0, n_bins, n_prev).astype(np.int32)
    dirv = rng.integers(0, 2, n_prev).astype(np.int32)
    feat[:2], thr[:2], dirv[:2] = (n_features - 1, 0), (n_bins - 1, 0), (1, 0)
    word = ts.SplitWord.of(n_features, n_bins, missing)
    assert word == ((n_bins - 1).bit_length(), int(missing))
    packed = word.pack(jnp.asarray(feat), jnp.asarray(thr),
                       jnp.asarray(dirv) if missing else None)
    assert packed.dtype == jnp.int32 and int(packed.min()) >= 0
    node = _nodes(rng, n_prev)
    for lookup in (ts.chain_select, ts.table_select):
        f, t, d = word.unpack(lookup(packed, jnp.asarray(node), n_prev))
        assert np.array_equal(np.asarray(f), _index(feat, node))
        assert np.array_equal(np.asarray(t), _index(thr, node))
        if missing:
            assert np.array_equal(np.asarray(d), _index(dirv, node))
        else:
            assert d is None


@pytest.mark.parametrize("n_features, n_bins, missing", [
    ((1 << 22) + 1, 256, True), ((1 << 23) + 1, 256, False),
    ((1 << 25) + 1, 64, False)])
def test_a_split_of_more_than_31_bits_is_refused(n_features, n_bins,
                                                 missing):
    with pytest.raises(Error, match="31"):
        ts.SplitWord.of(n_features, n_bins, missing)
    ts.SplitWord.of(n_features - 1, n_bins, missing)
    # where the plan is made, and only where a level packs
    m = HistGBT(mesh=local_mesh(1), n_trees=1, max_depth=3, n_bins=n_bins)
    m._missing = missing
    assert m._round_plan(n_features, ts.CHAIN_MIN_ROWS - 1).route_word is None
    with pytest.raises(Error, match="31"):
        m._round_plan(n_features, ts.CHAIN_MIN_ROWS)


def test_the_form_comes_from_two_shapes():
    assert (ts.CHAIN_MAX_ENTRIES, ts.CHAIN_MIN_ROWS) == (32, 1 << 21)
    for n_prev in (1, 2, 32, 64, 256):
        assert ts.route_form(n_prev, (1 << 21) - 1) == "tables"
        assert ts.route_form(n_prev, 1 << 21) == (
            "chain" if n_prev <= 32 else "pieces")
    # a mesh's rows are the device's own: 24M over four chips chains,
    # 3.77M over four does not
    m = HistGBT(mesh=local_mesh(4), n_trees=1, max_depth=6)
    assert set(m._round_plan(28, 24_000_000).route_forms) == {"chain"}
    assert set(m._round_plan(136, 3_771_136).route_forms) == {"tables"}


def _labels(X, y, num_class):
    if num_class == 1:
        return y, {}
    yk = np.digitize(np.nan_to_num(X[:, 0]) + y, [0.0, 1.0]).astype(np.float32)
    return yk, {"objective": "multi:softmax", "num_class": num_class}


def _traced_index(table, node, n_entries):
    """Plain indexing traced into the program: exact for the integers
    ``route`` looks up, under ``vmap`` and ``shard_map`` alike."""
    inside = (node >= 0) & (node < n_entries)
    return jnp.where(inside, table[jnp.clip(node, 0, n_entries - 1)], 0)


@pytest.mark.parametrize("depth, nan_share, num_class, devices, chain_max", [
    (6, 0.0, 1, 1, 32), (6, 0.3, 3, 1, 32), (6, 0.3, 1, 4, 32),
    (8, 0.0, 1, 1, 32), (8, 0.3, 1, 1, 32), (8, 0.0, 3, 4, 32),
    (8, 0.3, 1, 4, 4), (8, 0.0, 1, 1, 64),
    (9, 0.3, 1, 1, 32), (9, 0.0, 1, 4, 32)])
def test_both_sides_of_both_constants_grow_the_trees_of_plain_indexing(
        depth, nan_share, num_class, devices, chain_max, monkeypatch):
    X, y = _data(nan_share=nan_share)
    y, kw = _labels(X, y, num_class)
    # 3000 rows: the three lookups a level the round program always had
    tables = _fit(depth, X, y, devices, **kw)
    assert tables.round_plan["route_lookups"] == {
        "form": "pieces", "packed": False, "chained_entries": 0}
    # the other side of the row constant: one packed word a level
    monkeypatch.setattr(ts, "CHAIN_MIN_ROWS", 0)
    monkeypatch.setattr(ts, "CHAIN_MAX_ENTRIES", chain_max)
    chained, piecewise = [], []

    def chain(table, node, n_entries):
        chained.append(n_entries)
        return ts.chain_select(table, node, n_entries)

    def pieces(table, node, n_entries):
        piecewise.append(n_entries)
        return ts.table_select(table, node, n_entries)

    monkeypatch.setattr(hg, "chain_select", chain)
    monkeypatch.setattr(hg, "table_select", pieces)
    packed = _fit(depth, X, y, devices, **kw)
    parents = [1 << lvl for lvl in range(depth - 1)]
    assert chained == [n for n in parents if n <= chain_max]
    assert packed.round_plan["route_lookups"] == {
        "form": "chain", "packed": True, "chained_entries": sum(chained)}
    # past the chain ONE piecewise lookup a level, then the tail's and
    # the leaf's as they were
    tail = [1 << (depth - 1)] * (3 if nan_share else 2) + [1 << depth]
    assert piecewise == [n for n in parents if n > chain_max] + tail

    def plain(table, node, n_entries):
        if not jnp.issubdtype(table.dtype, jnp.integer):
            return ts.table_select(table, node, n_entries)
        return _traced_index(table, node, n_entries)

    monkeypatch.setattr(hg, "chain_select", plain)
    monkeypatch.setattr(hg, "table_select", plain)
    indexed = _fit(depth, X, y, devices, **kw)
    keys = ["feat", "thr", "gain", "leaf"] + (["dir"] if nan_share else [])
    _same_fit(tables, packed, keys)
    _same_fit(tables, indexed, keys)
    assert np.array_equal(_bits(tables.predict(X[:256])),
                          _bits(packed.predict(X[:256])))


# sha256 of ``str(jax.make_jaxpr(round program))`` on the parent commit
# (cd483b9): 3000 rows x 6 features, 32 bins, two rounds, by (depth,
# missing, classes, devices).  Below ``CHAIN_MIN_ROWS`` the level loop is
# the parent's, equation for equation; a PR that changes the round
# program on purpose re-reads these there.
_PARENT_ROUND_JAXPR = {
    (6, False, 1, 1):
        "34425877a9b7d90814ce314a9f80e328b6da329bc126fcdc61bc62a5c8622d2c",
    (8, False, 1, 1):
        "b7bda53d698068a55bbe2db43ab984f839f3342f9da2712f63d7dbc74bc6b334",
    (8, True, 1, 1):
        "11dd5caa0a002e619d3431889f673dff4622e739b550d4fbe09299594ac38544",
    (6, False, 3, 1):
        "4b7cf09bc27db86fd66878c7c3391d26027f155a2a0afb436acc33f0dcd8dc84",
    (8, True, 1, 4):
        "f28e59bc83e2fbb70e22099f681ea997367592b9874805fe30c8b9a02cff0042",
}


def _round_jaxpr(depth, missing, num_class, devices, n=3000, F=6):
    kw = ({"objective": "multi:softmax", "num_class": num_class}
          if num_class > 1 else {})
    m = HistGBT(mesh=local_mesh(devices), n_trees=2, max_depth=depth,
                n_bins=32, learning_rate=0.1, **kw)
    m._missing = missing
    fn = m._build_round_fn(m._round_plan(F, n), 2)
    margin = (num_class, n) if num_class > 1 else (n,)
    args = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in (
        ((F, n), np.uint8), ((n,), np.float32), ((n,), np.float32),
        (margin, np.float32))]
    return m.round_plan["route_lookups"], str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("depth, missing, num_class, devices",
                         sorted(_PARENT_ROUND_JAXPR))
def test_below_the_row_constant_the_round_program_is_the_parents_text(
        depth, missing, num_class, devices):
    lookups, text = _round_jaxpr(depth, missing, num_class, devices)
    assert lookups == {"form": "pieces", "packed": False,
                       "chained_entries": 0}
    assert hashlib.sha256(text.encode()).hexdigest() == \
        _PARENT_ROUND_JAXPR[(depth, missing, num_class, devices)]


@pytest.mark.parametrize("depth, missing, entries",
                         [(6, False, 31), (8, False, 63), (8, True, 63)])
def test_the_plan_counts_what_the_trace_chains(depth, missing, entries,
                                               monkeypatch):
    monkeypatch.setattr(ts, "CHAIN_MIN_ROWS", 0)
    lookups, text = _round_jaxpr(depth, missing, 1, 1)
    assert lookups == {"form": "chain", "packed": True,
                       "chained_entries": entries}
    assert hashlib.sha256(text.encode()).hexdigest() != \
        _PARENT_ROUND_JAXPR[(depth, missing, 1, 1)]
