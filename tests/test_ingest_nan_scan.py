"""ISSUE 50: the missing mode of a first ingest is settled from ONE scan
of the matrix where the cut sort's put has laid it (``ops.quantile.
nan_scan``), not from the host's passes over it.

(a) the device's three facts are the host's — any NaN, the share of the
cells to the last digit, each column's finiteness — on the matrices that
tell ``isnan`` from ``isfinite``; (b) the decision is ONE: its three
errors come word for word from either source; (c) cuts and model bytes
do not depend on the source, on one slab, many slabs, the pieces past
the put cliff and a four-device mesh; (d) the record says which source
ran, and the host's is left where no whole matrix is put.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlc_core_tpu.base.logging import Error
from dmlc_core_tpu.models import HistGBT
from dmlc_core_tpu.models import histgbt as G
from dmlc_core_tpu.ops.quantile import nan_scan
from dmlc_core_tpu.parallel.mesh import local_mesh
from dmlc_core_tpu.utils import profiler


def _matrix(kind, n=1500, F=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, F)).astype(np.float32)
    if kind == "scattered":
        X[rng.random((n, F)) < 0.37] = np.nan
        X[0] = 1.0                       # every column keeps a value
    elif kind == "nan_and_inf_column":
        X[rng.random((n, F)) < 0.2] = np.nan
        X[:, 2] = np.nan
        X[::3, 2] = np.inf
        X[1::3, 2] = -np.inf
    elif kind == "inf_beside_values":
        X[::5, 1] = np.inf
        X[1::5, 4] = -np.inf
    else:
        assert kind == "dense"
    return X


def _labels(X):
    return (np.nan_to_num(X[:, 0], posinf=0.0, neginf=0.0) > 0
            ).astype(np.float32)


def _model(**kw):
    kw = {"n_trees": 2, "max_depth": 3, "n_bins": 16, **kw}
    kw.setdefault("mesh", local_mesh(1))
    return HistGBT(**kw)


def _ingest(model, X, y, **kw):
    before = len(profiler.op_log())
    handle = model.make_device_data(X, y, **kw)
    if model._pending_warmup is not None:
        model._pending_warmup.join()
    (rec,) = [r for r in profiler.op_log()[before:]
              if r["name"] == "dmlc.ingest"]
    return handle, rec


@pytest.fixture
def host_source(monkeypatch):
    """A first ingest made to read its facts on the host, as every
    ingest did: the matrix is put all the same."""
    monkeypatch.setattr(
        HistGBT, "_nan_facts_device",
        staticmethod(lambda x, n_rows=None, mesh=None:
                     HistGBT._nan_facts_host(np.asarray(x)[:n_rows])))


# -- (a) the facts ------------------------------------------------------------

@pytest.mark.parametrize("kind", ["dense", "scattered", "nan_and_inf_column",
                                  "inf_beside_values"])
def test_the_device_reads_what_the_host_reads(kind):
    X = _matrix(kind)
    has_d, share_d, finite_d = HistGBT._nan_facts_device(jnp.asarray(X))
    has_h, share_h, finite_h = HistGBT._nan_facts_host(X)
    assert (has_d, share_d) == (has_h, share_h)           # to the last digit
    assert type(has_d) is bool and type(share_d) is float
    assert np.array_equal(finite_d(), finite_h())
    assert finite_d().dtype == np.bool_
    # NumPy's meanings: inf is neither NaN nor finite
    count, _ = jax.device_get(nan_scan(jnp.asarray(X)))
    assert count.dtype == np.int32
    assert np.array_equal(count, np.isnan(X).sum(axis=0))
    if kind == "nan_and_inf_column":
        assert not finite_d()[2] and finite_d().sum() == X.shape[1] - 1
    if kind == "inf_beside_values":
        assert not has_d and finite_d().all()


def test_the_cells_are_summed_in_python_ints(monkeypatch):
    """A column's count fits int32; the matrix's need not (40M x 28 is
    over 2**30, and Bosch x 2 would pass 2**31): columns that report
    2**30 NaN each are added up without wrapping."""
    per_column = np.full(5, 2 ** 30, np.int32)
    monkeypatch.setattr(G, "nan_scan",
                        lambda x: (per_column, np.ones(5, np.bool_)))
    x = jnp.zeros((4, 5), jnp.float32)
    has_nan, share, _ = HistGBT._nan_facts_device(x)
    assert has_nan and share == 5 * 2 ** 30 / 20 > 2 ** 26
    assert int(per_column.sum(dtype=np.int32)) != 5 * 2 ** 30   # it would wrap


def test_the_scan_has_a_device_scope_of_its_own():
    """``dmlc.cuts`` stays the summary and the merge: the scan's two
    column sums are named ``dmlc.cuts.nan_scan`` (that they are ONE read
    of the matrix on the chip, and hold nothing of its size:
    ``tests/test_hist_features.py``, with the chip's compiler)."""
    from dmlc_core_tpu.ops.quantile import local_summary

    x = jax.ShapeDtypeStruct((4096, 7), jnp.float32)
    assert "dmlc.cuts.nan_scan" in nan_scan.lower(x).as_text(debug_info=True)
    assert "nan_scan" not in local_summary.lower(x, None, 16).as_text(
        debug_info=True)


# -- (b) the one decision ------------------------------------------------------

ALL_NAN = "a feature is all-NaN: drop it or impute"
TWO_BINS = "NaN features need n_bins >= 3 (one bin is reserved for missing)"
NO_MISSING_BIN = ("X contains NaN but this model's bins were built without "
                  "a missing bin — refit from scratch (NaN in the first fit "
                  "enables missing support) or impute")


@pytest.mark.parametrize("source", ["device", "host"])
@pytest.mark.parametrize("case, message", [("all_nan", ALL_NAN),
                                           ("two_bins", TWO_BINS)])
def test_the_errors_are_the_same_from_either_source(case, message, source,
                                                    request):
    if source == "host":
        request.getfixturevalue("host_source")
    X = _matrix("nan_and_inf_column" if case == "all_nan" else "scattered")
    model = _model(n_bins=2 if case == "two_bins" else 16)
    with pytest.raises(Error) as err:
        model.make_device_data(X, _labels(X))
    assert message in str(err.value)
    assert model.cuts is None and not model._missing


def test_nan_at_a_model_without_a_missing_bin_is_refused_on_the_host():
    """A model that has cuts puts no whole matrix: the host reads the
    facts, and the refusal comes before anything is put."""
    X = _matrix("dense")
    model = _model()
    _ingest(model, X, _labels(X))
    holes = _matrix("scattered", seed=1)
    with pytest.raises(Error) as err:
        model.make_device_data(holes, _labels(holes))
    assert NO_MISSING_BIN in str(err.value)
    assert not model._missing


# -- (c) the same cuts, the same model ------------------------------------------

def _fit_bytes(tmp_path, name, X, y, **kw):
    model = _model(**kw)
    model.fit(X, y)
    path = str(tmp_path / name)
    model.save_model(path)
    with open(path, "rb") as f:
        return model, f.read()


@pytest.mark.parametrize("kind", ["dense", "scattered"])
def test_cuts_and_model_bytes_do_not_depend_on_the_source(kind, tmp_path,
                                                          request):
    X = _matrix(kind, n=2000)
    y = _labels(X)
    dev, dev_bytes = _fit_bytes(tmp_path, "device", X, y)
    request.getfixturevalue("host_source")
    host, host_bytes = _fit_bytes(tmp_path, "host", X, y)
    assert dev._missing == host._missing == (kind == "scattered")
    assert np.asarray(dev.cuts).tobytes() == np.asarray(host.cuts).tobytes()
    assert dev_bytes == host_bytes


@pytest.mark.parametrize("kind", ["dense", "scattered"])
@pytest.mark.parametrize("path", ["pieces", "slabs", "mesh4"])
def test_every_put_path_settles_the_same_mode_and_cuts(kind, path,
                                                       monkeypatch):
    """One slab is the reference; the matrix in pieces past the put cliff,
    the multi-slab stream and a four-device mesh (device 0 holds the cut
    matrix, chips 1-3 are not asked) scan what they put."""
    X = _matrix(kind, n=2048)
    y = _labels(X)
    ref = _model()
    h_ref, rec_ref = _ingest(ref, X, y)
    if path == "pieces":
        monkeypatch.setattr(G, "_PUT_CLIFF_BYTES", 1000)
        monkeypatch.setattr(G, "_PUT_PIECE_BYTES", 9000)
    elif path == "slabs":
        monkeypatch.setenv("DMLC_INGEST_CHUNK_ROWS", "512")
    model = _model(mesh=local_mesh(4) if path == "mesh4" else local_mesh(1))
    h, rec = _ingest(model, X, y)
    assert rec["counts"]["nan_scan"] == "device"
    assert "dmlc.ingest.host_prep.nan_scan" not in rec["children"]
    assert rec["children"]["dmlc.ingest.cuts.nan_scan"][0] == 1
    if path == "pieces":
        assert rec["children"]["dmlc.ingest.put"][0] > 1
    assert model._missing == ref._missing == (kind == "scattered")
    assert (rec["counts"]["missing"], rec["counts"]["missing_share"]) == \
        (rec_ref["counts"]["missing"], rec_ref["counts"]["missing_share"])
    assert rec["counts"]["missing_share"] == np.isnan(X).mean()
    assert np.asarray(model.cuts).tobytes() == np.asarray(ref.cuts).tobytes()
    assert np.array_equal(np.asarray(h["bins_t"])[:, :len(y)],
                          np.asarray(h_ref["bins_t"])[:, :len(y)])


# -- (d) which source ran ---------------------------------------------------------

def test_the_record_says_which_source_ran():
    X = _matrix("scattered")
    y = _labels(X)
    first = _model()
    _, rec = _ingest(first, X, y)
    assert rec["counts"]["nan_scan"] == "device"
    n, seconds, _longest, nbytes = rec["children"]["dmlc.ingest.cuts.nan_scan"]
    assert (n, nbytes) == (1, X.nbytes) and seconds > 0
    assert "dmlc.ingest.host_prep.nan_scan" not in rec["children"]
    # cuts= passed: no whole matrix is put, the host reads the facts
    given = _model()
    given._missing = True              # the cuts are a missing-mode model's
    _, rec = _ingest(given, X, y, cuts=first.cuts)
    assert rec["counts"]["nan_scan"] == "host"
    assert rec["children"]["dmlc.ingest.host_prep.nan_scan"][3] == X.nbytes
    assert "dmlc.ingest.cuts" not in rec["children"]
    assert rec["counts"]["missing_share"] == np.isnan(X).mean()
    # a model that has cuts (an eval handle, a continued fit): the same
    _, rec = _ingest(first, X, y)
    assert rec["counts"]["nan_scan"] == "host"
    assert "dmlc.ingest.cuts.nan_scan" not in rec["children"]
