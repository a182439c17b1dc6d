"""The staged round through the Pallas kernel: whole fits, analytics.

* a ONE-tree fit with ``hist_method="pallas"`` (``dmlc_hist``,
  interpreted off-TPU) grows the tree arrays of the ``segment`` fit,
  byte for byte, across {depthwise, lossguide} x {plain, packed bins,
  feature bundling} x {6, 28, 31 features}: tree 0's gradients are
  +-0.5 and its hessians 0.25, exact in bfloat16 and in any order of
  summation.  One tree, and the arrays, not ``save_model`` bytes: the
  file holds the param's ``hist_method`` string, and from tree 1 on the
  kernel rounds real float32 gradients to bfloat16 where ``segment``
  sums them as they are — those trees differ by design.  These fits
  are what holds ``grow_tree``'s sibling subtraction to a reference;
* ``auto`` plans ``segment`` where a packed layout's rows fit no
  kernel block, and the model fits so;
* the analytic traffic model (``bins_bytes_per_round``) prices the
  staged round's passes over the bin matrix.
"""

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dmlc_core_tpu.models import HistGBT  # noqa: E402
from dmlc_core_tpu.ops.histogram import bins_bytes_per_round  # noqa: E402
from dmlc_core_tpu.parallel.mesh import local_mesh  # noqa: E402

MODEL_KW = dict(n_trees=1, max_depth=3, n_bins=32,
                objective="binary:logistic", learning_rate=0.3)


def _narrow_xy(n=1503, F=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    X[:, 1] = rng.integers(0, 3, n)
    X[:, 3] = rng.integers(0, 2, n)
    X[:, 5] = rng.integers(0, 5, n)
    y = ((X[:, 0] + 0.5 * X[:, 1] - X[:, 3]) > 0).astype(np.float32)
    return X, y


def _bundle_xy(n=1404, seed=4):
    # two mutually-exclusive one-hot columns so DMLC_FEATURE_BUNDLE fires
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 5)).astype(np.float32)
    onehot = rng.integers(0, 3, n)
    X[:, 2] = (onehot == 1).astype(np.float32)
    X[:, 3] = (onehot == 2).astype(np.float32)
    y = ((X[:, 0] + X[:, 2] - X[:, 3]) > 0).astype(np.float32)
    return X, y


def _wide_xy(F, n=1302, seed=11):
    # HIGGS's 28 features and 31: the kernels' blocks are padded to 32
    # rows and the last group of 8 is partly pad features (ISSUE 34)
    rng = np.random.default_rng(seed + F)
    X = rng.normal(size=(n, F)).astype(np.float32)
    y = ((X[:, 0] - X[:, F - 1] + 0.5 * X[:, F // 2]) > 0).astype(np.float32)
    return X, y


def _tree_bytes(method, X, y, **kw):
    m = HistGBT(mesh=local_mesh(1), hist_method=method,
                **dict(MODEL_KW, **kw))
    m.fit(X, y)
    assert set(m.round_plan["hist_method"]) == {method}
    (tree,) = m.trees
    return {k: np.asarray(v).tobytes() for k, v in tree.items()}, m


class TestPallasFitEqualsSegmentFit:
    # every lever the staged round composes with; lossguide rides
    # max_leaves so the expansion loop (not the level loop) is hit
    CASES = [
        ("depthwise_plain", {}, _narrow_xy),
        ("depthwise_pack", {"DMLC_BIN_PACK": "1"}, _narrow_xy),
        ("depthwise_bundle", {"DMLC_FEATURE_BUNDLE": "1"}, _bundle_xy),
        ("lossguide_plain", {"grow_policy": "lossguide",
                             "max_leaves": 6}, _narrow_xy),
        ("lossguide_pack", {"grow_policy": "lossguide",
                            "max_leaves": 6,
                            "DMLC_BIN_PACK": "1"}, _narrow_xy),
        ("lossguide_bundle", {"grow_policy": "lossguide",
                              "max_leaves": 6,
                              "DMLC_FEATURE_BUNDLE": "1"}, _bundle_xy),
        ("depthwise_28_features", {}, lambda: _wide_xy(28)),
        ("depthwise_31_features", {}, lambda: _wide_xy(31)),
        ("lossguide_28_features", {"grow_policy": "lossguide",
                                   "max_leaves": 6},
         lambda: _wide_xy(28)),
    ]

    @pytest.mark.parametrize("name,env,mk", CASES,
                             ids=[c[0] for c in CASES])
    def test_pallas_tree_is_the_segment_tree(self, name, env, mk,
                                             monkeypatch):
        X, y = mk()
        # DMLC_* names are the environment's, the others the Parameter's
        kw = {k: v for k, v in env.items() if not k.startswith("DMLC_")}
        for k in env.keys() - kw.keys():
            monkeypatch.setenv(k, env[k])
        want, _ = _tree_bytes("segment", X, y, **kw)
        got, m = _tree_bytes("pallas", X, y, **kw)
        assert got == want
        assert np.asarray(m.trees[0]["gain"]).any()     # a grown tree
        if "DMLC_BIN_PACK" in env or "DMLC_FEATURE_BUNDLE" in env:
            assert m._bin_layout is not None    # the lever actually fired


def test_auto_plans_segment_where_a_packed_layout_fits_no_block(
        monkeypatch):
    """A nibble-packed layout cannot be cut on features, so past the
    kernel's 392-row block ``auto`` on a TPU has no Pallas build to
    plan: every level reads ``segment``, on record before anything
    traces, and the fit runs it."""
    monkeypatch.setenv("DMLC_BIN_PACK", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rng = np.random.default_rng(2)
    X = rng.integers(0, 3, size=(300, 800)).astype(np.float32)
    y = (X[:, 0] + X[:, 799] > 2).astype(np.float32)
    m = HistGBT(mesh=local_mesh(1), hist_method="auto", n_trees=2,
                max_depth=3, n_bins=32, objective="binary:logistic")
    m.fit(X, y)
    lay = m._bin_layout
    assert lay.pairs and lay.phys_rows > 392
    assert m.round_plan["hist_method"] == ["segment"] * 3
    assert m.round_plan["hist_node_blocks"] == [[]] * 3
    assert m.round_plan["hist_feature_blocks"] == [[]] * 3
    assert np.asarray(m.trees[0]["gain"]).any()


class TestAnalyticModel:
    def test_bins_bytes_staged_passes(self):
        rows, rb = 10_000_000, 28
        # depthwise: a histogram pass a level, a descend pass below the
        # root and one for the leaves
        assert bins_bytes_per_round(6, rows, rb) == 11 * rows * rb
        # lossguide: 2*leaves-1
        assert bins_bytes_per_round(
            6, rows, rb, grow_policy="lossguide", max_leaves=8) \
            == 15 * rows * rb
        # degenerate depth never prices zero passes
        assert bins_bytes_per_round(1, rows, rb) == rows * rb
