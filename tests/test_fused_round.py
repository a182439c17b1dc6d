"""Fully-fused round kernel: byte parity, analytics.

The ISSUE 18 contracts:

* ``DMLC_FUSED_ROUND=1`` (one Pallas program per level / expansion:
  bin-read -> descend -> g/h accumulate -> sibling subtraction, all
  VMEM-resident) serializes byte-identically to the staged
  three-dispatch path across {depthwise, lossguide} x {packed bins,
  feature bundling} — with ``hist_method="pallas"`` pinned, since byte
  parity of f32 sums requires BOTH paths to share the pallas
  accumulation order (tree 0's g/h are bf16-exact so any order matches;
  later trees are order-sensitive);
* ``DMLC_FUSED_ROUND=0`` restores the seed path exactly (same bytes as
  an unset knob on a non-TPU backend, where ``auto`` never engages);
* the analytic traffic model (``bins_bytes_per_round(fused=...)``)
  prices the fused round's passes over the bin matrix.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dmlc_core_tpu.models import HistGBT  # noqa: E402
from dmlc_core_tpu.ops.histogram import (bins_bytes_per_round,  # noqa: E402
                                         fused_round_ok)
from dmlc_core_tpu.parallel.mesh import local_mesh  # noqa: E402

# hist_method pinned to pallas: the fused kernel accumulates in pallas
# tile order, and f32 byte parity beyond tree 0 requires the unfused
# reference to sum in the SAME order ("auto" resolves to segment on CPU)
MODEL_KW = dict(n_trees=3, max_depth=3, n_bins=32, hist_method="pallas",
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


def _fit_bytes(path, X, y):
    m = HistGBT(mesh=local_mesh(1), **MODEL_KW)
    m.fit(X, y)
    m.save_model(str(path))
    return path.read_bytes(), m


class TestFusedByteParity:
    # every lever combo the fused kernel composes with; lossguide rides
    # DMLC_MAX_LEAVES so the expansion loop (not the level loop) is hit
    CASES = [
        ("depthwise_plain", {}, _narrow_xy),
        ("depthwise_pack", {"DMLC_BIN_PACK": "1"}, _narrow_xy),
        ("depthwise_bundle", {"DMLC_FEATURE_BUNDLE": "1"}, _bundle_xy),
        ("lossguide_plain", {"DMLC_GROW_POLICY": "lossguide",
                             "DMLC_MAX_LEAVES": "6"}, _narrow_xy),
        ("lossguide_pack", {"DMLC_GROW_POLICY": "lossguide",
                            "DMLC_MAX_LEAVES": "6",
                            "DMLC_BIN_PACK": "1"}, _narrow_xy),
        ("lossguide_bundle", {"DMLC_GROW_POLICY": "lossguide",
                              "DMLC_MAX_LEAVES": "6",
                              "DMLC_FEATURE_BUNDLE": "1"}, _bundle_xy),
        ("depthwise_28_features", {}, lambda: _wide_xy(28)),
        ("depthwise_31_features", {}, lambda: _wide_xy(31)),
        ("lossguide_28_features", {"DMLC_GROW_POLICY": "lossguide",
                                   "DMLC_MAX_LEAVES": "6"},
         lambda: _wide_xy(28)),
    ]

    @pytest.mark.parametrize("name,env,mk", CASES,
                             ids=[c[0] for c in CASES])
    def test_fused_matches_unfused_bytes(self, name, env, mk,
                                         monkeypatch, tmp_path):
        X, y = mk()
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        monkeypatch.setenv("DMLC_FUSED_ROUND", "0")
        b0, _ = _fit_bytes(tmp_path / "unfused.gbt", X, y)
        monkeypatch.setenv("DMLC_FUSED_ROUND", "1")
        b1, m1 = _fit_bytes(tmp_path / "fused.gbt", X, y)
        assert b0 == b1
        if "DMLC_BIN_PACK" in env or "DMLC_FEATURE_BUNDLE" in env:
            assert m1._bin_layout is not None    # the lever actually fired

    def test_fused_round_0_restores_seed_path(self, monkeypatch, tmp_path):
        # the off switch IS the seed path: on a non-TPU backend "auto"
        # never engages, so unset-knob bytes == explicit-0 bytes
        X, y = _narrow_xy(seed=7)
        monkeypatch.delenv("DMLC_FUSED_ROUND", raising=False)
        b_auto, _ = _fit_bytes(tmp_path / "auto.gbt", X, y)
        monkeypatch.setenv("DMLC_FUSED_ROUND", "0")
        b_off, _ = _fit_bytes(tmp_path / "off.gbt", X, y)
        assert b_auto == b_off


class TestAnalyticModel:
    def test_bins_bytes_fused_passes(self):
        rows, rb = 10_000_000, 28
        # depthwise: 2*depth-1 staged passes collapse to depth
        assert bins_bytes_per_round(6, rows, rb) == 11 * rows * rb
        assert bins_bytes_per_round(6, rows, rb, fused=True) \
            == 6 * rows * rb
        # lossguide: 2*leaves-1 -> leaves
        assert bins_bytes_per_round(
            6, rows, rb, grow_policy="lossguide", max_leaves=8,
            fused=True) == 8 * rows * rb
        assert bins_bytes_per_round(
            6, rows, rb, grow_policy="lossguide", max_leaves=8) \
            == 15 * rows * rb
        # degenerate depth never prices zero passes
        assert bins_bytes_per_round(1, rows, rb, fused=True) \
            == rows * rb

    def test_fused_round_ok_vmem_gate(self):
        # flagship shape fits; a pathological node count does not
        assert fused_round_ok(256, 28, n_prev=16)
        assert not fused_round_ok(256, 2048, n_prev=4096)
