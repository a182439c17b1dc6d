"""Packed narrow bins (int4) + exclusive feature bundling: layout unit
tests and the bit-parity contracts of ISSUE 12.

The seed's eps-bumped quantile sketch SPREADS a low-cardinality
feature's bin ids across [0, n_bins) — a 3-valued feature lands at e.g.
{0, 11, 22} — so the layout compact-remaps occupied ids to dense
[0, count).  The parity oracle: the remap only RELABELS histogram
cells, so after ``unbundle_hist`` scatters them back to original
positions, every histogram method must reproduce the plain build
bit-for-bit (gradients chosen bf16-exact so even the MXU methods'
reduction-order differences cannot produce last-ulp drift).
"""

import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dmlc_core_tpu.models import HistGBT  # noqa: E402
from dmlc_core_tpu.ops import binlayout as bl  # noqa: E402
from dmlc_core_tpu.ops.histogram import (build_histogram,  # noqa: E402
                                         hist_psum_bytes_per_round,
                                         select_feature_bins)


def _spread_bins(rng, n, F, B, narrow=()):
    """[F, n] bin matrix mimicking the eps-bumped sketch: narrow
    features occupy FEW, SPREAD-OUT ids (not a dense prefix); wide
    features cover every bin deterministically."""
    bins = np.zeros((F, n), np.uint8)
    for f in range(F):
        if f in narrow:
            k = int(rng.integers(2, 7))
            ids = np.sort(rng.choice(B, size=k, replace=False))
            bins[f] = ids[rng.integers(0, k, n)]
        else:
            bins[f] = (np.arange(n) + f) % B
    return bins


def _counts(bins, B):
    return bl.bin_counts(jnp.asarray(bins), B)


def _exclusive_bins(rng, n, B=32):
    """Two near-one-hot features whose DEFAULT bin is NOT 0 (the
    quantile sketch maps the common value wherever it likes) plus a
    wide feature; the one-hots never fire on the same row."""
    bins = np.zeros((3, n), np.uint8)
    bins[0] = np.arange(n) % B
    onehot = rng.integers(0, 3, n)
    bins[1] = np.where(onehot == 1, 20, 5)
    bins[2] = np.where(onehot == 2, 25, 7)
    return bins


class TestLayout:
    def test_all_wide_is_trivial(self, rng):
        bins = _spread_bins(rng, 500, 4, 32, narrow=())
        assert bl.compute_layout(_counts(bins, 32), 4, 32) is None

    def test_pack_off_is_trivial(self, rng):
        bins = _spread_bins(rng, 500, 6, 32, narrow=(1, 3, 5))
        assert bl.compute_layout(_counts(bins, 32), 6, 32,
                                 pack=False) is None

    def test_narrow_features_pair(self, rng):
        bins = _spread_bins(rng, 500, 9, 32, narrow=(1, 4, 7, 8))
        lay = bl.compute_layout(_counts(bins, 32), 9, 32)
        assert lay is not None
        assert len(lay.pairs) == 2 and lay.storage_features == 9
        assert lay.sync_bins == 32            # wide features keep width
        # every narrow feature carries a compact remap of its used ids
        for f in (1, 4, 7, 8):
            occ = lay.bin_maps[f]
            assert occ is not None and len(occ) <= bl.PACK_WIDTH
            assert set(occ) == set(np.unique(bins[f]))

    def test_counts_mask_padding_rows(self, rng):
        bins = _spread_bins(rng, 500, 3, 32, narrow=(1,))
        padded = np.concatenate([bins, np.zeros((3, 36), np.uint8)], axis=1)
        c_real = bl.bin_counts(jnp.asarray(bins), 32)
        c_mask = bl.bin_counts(jnp.asarray(padded), 32, n_valid=500)
        assert np.array_equal(c_real, c_mask)

    def test_select_bins_roundtrip(self, rng):
        bins = _spread_bins(rng, 603, 9, 32, narrow=(1, 4, 7, 8))
        lay = bl.compute_layout(_counts(bins, 32), 9, 32)
        phys = bl.pack_matrix(jnp.asarray(bins), lay)
        assert phys.shape[0] == lay.phys_rows
        for f in range(9):
            sel = jnp.full(603, f, jnp.int32)
            got = np.asarray(select_feature_bins(phys, sel, layout=lay))
            assert np.array_equal(got, bins[f]), f

    def test_psum_model_shrinks_with_layout(self, rng):
        bins = _spread_bins(rng, 500, 8, 32, narrow=(0, 1, 2, 3, 4, 5))
        lay = bl.compute_layout(_counts(bins, 32), 8, 32)
        base = hist_psum_bytes_per_round(3, 8, 32)
        packed = hist_psum_bytes_per_round(3, 8, 32, layout=lay)
        assert packed == base                  # S and Bs unchanged: 8, 32
        # lossguide builds one node per expansion instead of 2^(l-1)
        lg = hist_psum_bytes_per_round(6, 8, 32, grow_policy="lossguide",
                                       max_leaves=8)
        assert lg == 8 * 2 * 8 * 32 * 4
        assert lg < hist_psum_bytes_per_round(6, 8, 32)


class TestPackedParity:
    @pytest.mark.parametrize("method", ["segment", "pallas"])
    def test_bit_parity_vs_plain(self, method, rng):
        n, F, B, N = 1021, 9, 32, 3            # odd row count on purpose
        bins = _spread_bins(rng, n, F, B, narrow=(1, 4, 7, 8))
        node = rng.integers(0, N, n).astype(np.int32)
        node[::7] = -1                         # padding rows drop out
        # bf16-exact gradients: sums are exact in f32, so ANY
        # reduction order must reproduce them bit-for-bit
        g = rng.choice([-1.0, -0.5, 0.5, 1.0], n).astype(np.float32)
        h = rng.choice([0.5, 1.0], n).astype(np.float32)
        plain = np.asarray(build_histogram(
            jnp.asarray(bins), jnp.asarray(node), jnp.asarray(g),
            jnp.asarray(h), N, B, method, transposed=True))
        lay = bl.compute_layout(_counts(bins, B), F, B)
        phys = bl.pack_matrix(jnp.asarray(bins), lay)
        hs = build_histogram(phys, jnp.asarray(node), jnp.asarray(g),
                             jnp.asarray(h), N, B, method,
                             transposed=True, layout=lay)
        got = np.asarray(bl.unbundle_hist(hs, lay, B))
        assert got.shape == plain.shape
        assert np.array_equal(got, plain), method


class TestBundling:
    def test_detect_and_exact_roundtrip(self, rng):
        n, B = 1021, 32
        bins = _exclusive_bins(rng, n, B)
        counts = _counts(bins, B)
        bundles = bl.detect_bundles(bins, np.asarray(counts), B)
        assert bundles == ((1, 2),)
        lay = bl.compute_layout(counts, 3, B, pack=False, bundles=bundles)
        assert lay is not None and lay.has_bundles
        assert lay.storage_features == 2       # 3 features -> 2 rows
        # default (most frequent) bin leads each member's compact map
        assert lay.bin_maps[1][0] == 5 and lay.bin_maps[2][0] == 7
        # decode round-trip through the fused row
        phys = bl.pack_matrix(jnp.asarray(bins), lay)
        for f in range(3):
            sel = jnp.full(n, f, jnp.int32)
            got = np.asarray(bl.select_bins(phys, sel, lay))
            assert np.array_equal(got, bins[f]), f

    def test_bundle_hist_parity(self, rng):
        n, B, N = 1021, 32, 2
        bins = _exclusive_bins(rng, n, B)
        node = rng.integers(0, N, n).astype(np.int32)
        g = rng.choice([-1.0, -0.5, 0.5, 1.0], n).astype(np.float32)
        h = rng.choice([0.5, 1.0], n).astype(np.float32)
        counts = _counts(bins, B)
        bundles = bl.detect_bundles(bins, np.asarray(counts), B)
        lay = bl.compute_layout(counts, 3, B, pack=False, bundles=bundles)
        plain = np.asarray(build_histogram(
            jnp.asarray(bins), jnp.asarray(node), jnp.asarray(g),
            jnp.asarray(h), N, B, "segment", transposed=True))
        hs = build_histogram(bl.pack_matrix(jnp.asarray(bins), lay),
                             jnp.asarray(node), jnp.asarray(g),
                             jnp.asarray(h), N, B, "segment",
                             transposed=True, layout=lay)
        got = np.asarray(bl.unbundle_hist(hs, lay, B))
        # bf16-exact gradients make even the tot − Σsegment default-bin
        # reconstruction exact (sums of halves are exact f32)
        assert np.array_equal(got, plain)

    def test_conflicting_features_not_bundled(self, rng):
        n, B = 800, 32
        bins = np.zeros((2, n), np.uint8)
        bins[0] = np.where(rng.random(n) < 0.3, 20, 5)
        bins[1] = np.where(rng.random(n) < 0.3, 25, 7)   # overlaps feat 0
        counts = _counts(bins, B)
        assert bl.detect_bundles(bins, np.asarray(counts), B) == ()


def _narrow_xy(n=1503, F=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    X[:, 1] = rng.integers(0, 3, n)
    X[:, 3] = rng.integers(0, 2, n)
    X[:, 5] = rng.integers(0, 5, n)
    y = ((X[:, 0] + 0.5 * X[:, 1] - X[:, 3]) > 0).astype(np.float32)
    return X, y


MODEL_KW = dict(n_trees=3, max_depth=3, n_bins=32,
                objective="binary:logistic", learning_rate=0.3)


class TestModelParity:
    def test_pack_on_off_byte_parity(self, tmp_path, monkeypatch):
        X, y = _narrow_xy()
        m0 = HistGBT(**MODEL_KW)
        m0.fit(X, y)
        monkeypatch.setenv("DMLC_BIN_PACK", "1")
        m1 = HistGBT(**MODEL_KW)
        m1.fit(X, y)
        assert m1._bin_layout is not None      # the lever actually fired
        u0, u1 = str(tmp_path / "a.ubj"), str(tmp_path / "b.ubj")
        m0.save_model(u0)
        m1.save_model(u1)
        assert open(u0, "rb").read() == open(u1, "rb").read()

    def test_no_bundle_fires_byte_parity(self, tmp_path, monkeypatch):
        # dense gaussian features: nothing is exclusive, bundling must
        # decline and leave the seed path byte-identical
        rng = np.random.default_rng(3)
        X = rng.normal(size=(900, 5)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        m0 = HistGBT(**MODEL_KW)
        m0.fit(X, y)
        monkeypatch.setenv("DMLC_FEATURE_BUNDLE", "1")
        m1 = HistGBT(**MODEL_KW)
        m1.fit(X, y)
        assert m1._bin_layout is None
        u0, u1 = str(tmp_path / "a.ubj"), str(tmp_path / "b.ubj")
        m0.save_model(u0)
        m1.save_model(u1)
        assert open(u0, "rb").read() == open(u1, "rb").read()

    def test_bundle_fires_same_structure(self, monkeypatch):
        rng = np.random.default_rng(4)
        n = 1404
        X = rng.normal(size=(n, 5)).astype(np.float32)
        onehot = rng.integers(0, 3, n)
        X[:, 2] = (onehot == 1).astype(np.float32)
        X[:, 3] = (onehot == 2).astype(np.float32)
        y = ((X[:, 0] + X[:, 2] - X[:, 3]) > 0).astype(np.float32)
        m0 = HistGBT(**MODEL_KW)
        m0.fit(X, y)
        monkeypatch.setenv("DMLC_FEATURE_BUNDLE", "1")
        m1 = HistGBT(**MODEL_KW)
        m1.fit(X, y)
        assert m1._bin_layout is not None and m1._bin_layout.has_bundles
        for t0, t1 in zip(m0.trees, m1.trees):
            assert np.array_equal(t0["feat"], t1["feat"])
            assert np.array_equal(t0["thr"], t1["thr"])
        np.testing.assert_allclose(m0.predict(X), m1.predict(X),
                                   rtol=1e-5, atol=1e-6)
