"""The share against the whole: a data-parallel fit on a mesh of 1, 2 and
4 chips, through the entry points a user calls (``make_device_data`` with
no ``cuts=``, ``fit_device``), held against the benchmark's plain float64
reference (``benchmark/reference.py``), which sees all rows and knows of
no shards — under the limits the shipped ``boost`` mix gives the
four-chip cell ``higgs-d6-dp4.boost``.  The chips' ``psum``-ed partial
histograms have to give the tree the unsharded reference gives.

The rows divide neither the ingest slabs nor the mesh evenly, so slabs
straddle the chips' row boundaries and the last chip carries pad rows.
"""

import json
import os

import numpy as np
import pytest

from benchmark import checks, datagen, reference as ref
from dmlc_core_tpu.models import HistGBT
from dmlc_core_tpu.ops.quantile import compute_cuts
from dmlc_core_tpu.parallel.mesh import local_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, SLAB_ROWS = 5003, 700
CFG = {"features": 8, "max_depth": 4, "n_bins": 64, "learning_rate": 0.3,
       "reg_lambda": 1.0, "min_child_weight": 1.0, "base_score": 0.0}
# of the cell's numbers, those a fit of a few rounds can be held to (the
# rest need a hundred rounds to learn, or a window of operations)
NUMBERS = ["bins_mismatches", "tree0.root_gain_gap",
           "tree0.reported_gain_gap", "tree0.leaf_gap", "tree1.leaf_gap",
           "ops_trees_differ"]


@pytest.fixture(scope="module")
def limits():
    with open(os.path.join(ROOT, "benchmark", "traffic", "boost.json")) as f:
        return json.load(f)["limits"]


@pytest.fixture(scope="module")
def rows():
    return datagen.higgs_like(ROWS, CFG["features"], 29)


@pytest.fixture(scope="module")
def whole(rows):
    """The uncut layer, computed once from all rows: the cuts as one
    device computes them (every mesh has to arrive at the same ones) and
    the reference's binned matrix against them."""
    X, _y = rows
    cuts = np.asarray(compute_cuts(X, CFG["n_bins"]))
    return cuts, np.ascontiguousarray(ref.bin_rows(X, cuts).T)


@pytest.mark.parametrize("chips", [1, 2, 4])
def test_mesh_fit_against_the_unsharded_reference(chips, rows, whole, limits,
                                                  monkeypatch):
    X, y = rows
    monkeypatch.setenv("DMLC_INGEST_CHUNK_ROWS", str(SLAB_ROWS))
    model = HistGBT(n_trees=4, mesh=local_mesh(chips),
                    **{k: CFG[k] for k in ("max_depth", "n_bins",
                                           "learning_rate")})
    handle = model.make_device_data(X, y)
    model.fit_device(handle)
    first = [{k: np.asarray(v) for k, v in t.items()} for t in model.trees]
    model.fit_device(handle)
    again = [{k: np.asarray(v) for k, v in t.items()} for t in model.trees]
    assert model.round_plan["mesh_devices"] == chips

    cuts, bins_t = whole
    assert np.array_equal(np.asarray(model.cuts), cuts)
    numbers = {"bins_mismatches": int(np.count_nonzero(
        np.asarray(handle["bins_t"])[:, :ROWS] != bins_t))}
    # the trees of the mesh against the reference on ITS OWN binned
    # matrix of all the rows, not on what the chips hold
    numbers.update(checks.boost_tree_numbers(bins_t, y, first, CFG))
    numbers["ops_trees_differ"] = checks.trees_differ(first, again)
    assert sorted(numbers) == sorted(NUMBERS)
    for name in NUMBERS:
        assert numbers[name] <= limits[name], (chips, name, numbers)
