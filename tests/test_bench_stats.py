"""bench.py anomaly machinery + rank-objective autodiff oracle.

The official BENCH record's trustworthiness rests on chunk_stats
flagging captures with a stalled dispatch; that logic must be tested, not just
shipped.  The second half verifies the RankNet pairwise gradients
against jax.grad/jax.hessian of the explicitly-summed pairwise loss —
an oracle stronger than the learning tests."""

import os
import sys

import pytest
import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import chunk_stats, scaling_summary  # noqa: E402


class TestChunkStats:
    def test_uniform_chunks_no_anomaly(self):
        ct = [(25, 3.0), (50, 6.1), (75, 9.1), (100, 12.2)]
        s = chunk_stats(ct, 100, 12.2)
        assert s["anomaly"] is False
        assert abs(s["rounds_per_sec_median_chunk"] - 25 / 3.05) < 0.2
        assert len(s["chunk_seconds_per_round"]) == 4

    def test_degraded_chunk_flags_anomaly(self):
        # one wedged dispatch: 25 rounds took 40s instead of ~3s —
        # the round-2 capture signature
        ct = [(25, 3.0), (50, 43.0), (75, 46.0), (100, 49.0)]
        s = chunk_stats(ct, 100, 49.0)
        assert s["anomaly"] is True
        # best-chunk still reports the healthy rate
        assert s["rounds_per_sec_best_chunk"] > 8.0

    def test_single_chunk_cannot_flag(self):
        s = chunk_stats([(25, 3.0)], 25, 3.0)
        assert s["anomaly"] is False

    def test_empty_falls_back_to_wall(self):
        s = chunk_stats([], 100, 50.0)
        assert s["anomaly"] is False
        assert s["rounds_per_sec_best_chunk"] == 2.0

    def test_zero_delta_clamps(self):
        # coarse timer on a fast local fit: two chunks arrive at the
        # SAME timestamp — must neither divide by zero nor spuriously
        # flag anomaly against a normal sibling chunk
        s = chunk_stats([(25, 1.0), (50, 1.0), (75, 2.0)], 75, 2.0)
        assert np.isfinite(s["rounds_per_sec_best_chunk"])
        assert np.isfinite(s["rounds_per_sec_median_chunk"])
        # the artifact makes normal siblings look 40000x "slower" than
        # the zero-delta chunk, but nothing is actually slow (40ms/round
        # < the 50ms/round stall floor) — must not flag
        assert s["anomaly"] is False

    def test_threshold_boundary(self):
        # exactly 3.0x is NOT an anomaly; just above is
        at = chunk_stats([(10, 1.0), (20, 4.0)], 20, 4.0)
        assert at["anomaly"] is False            # ratio == 3.0
        above = chunk_stats([(10, 1.0), (20, 4.2)], 20, 4.2)
        assert above["anomaly"] is True


class TestScalingSummary:
    def test_perfect_linear_scaling(self):
        s = scaling_summary(8, per_chip_rate=2.0, baseline_rate=2.0)
        assert s["scaling_efficiency"] == 1.0
        assert s["aggregate_rounds_per_sec"] == 16.0
        assert s["chips"] == 8 and s["baseline_chips"] == 1

    def test_issue7_acceptance_bar(self):
        # 8 chips at 70% of the 1-chip per-chip rate = the 0.7 bar
        s = scaling_summary(8, per_chip_rate=1.4, baseline_rate=2.0)
        assert abs(s["scaling_efficiency"] - 0.7) < 1e-9
        assert s["baseline_rounds_per_sec_per_chip"] == 2.0

    def test_superlinear_allowed(self):
        # out-of-core relief: N chips can beat N x 1-chip when the
        # 1-chip run was HBM-thrashing — the summary must not clamp
        s = scaling_summary(4, per_chip_rate=2.5, baseline_rate=2.0)
        assert s["scaling_efficiency"] == 1.25

    def test_degenerate_baseline_returns_none(self):
        assert scaling_summary(8, 2.0, 0.0) is None
        assert scaling_summary(8, 2.0, None) is None
        assert scaling_summary(0, 2.0, 2.0) is None


class TestPairwiseRankAutodiffOracle:
    @pytest.mark.slow
    def test_grad_and_hessian_match_autodiff(self):
        """g must equal jax.grad of the summed pairwise loss and h the
        exact diagonal of its Hessian (RankNet's per-pair rho sums ARE
        the diagonal, not an approximation)."""
        from dmlc_core_tpu.models.histgbt import _PairwiseRank

        rng = np.random.default_rng(0)
        lens = np.array([5, 3, 9, 1, 4])         # ragged: three buckets' worth
        n = int(lens.sum())
        pred = jnp.asarray(rng.normal(size=n).astype(np.float32))
        rel = rng.integers(0, 3, size=n).astype(np.float32)
        # a budget of 128 pair slots a block exercises query padding
        obj, table, _ = _PairwiseRank.from_queries([lens], [rel], n,
                                                   pair_slots=128)
        table = jax.tree.map(jnp.asarray, table)
        rel_j = jnp.asarray(rel)
        bounds = np.r_[0, np.cumsum(lens)]

        def total_loss(s):
            loss = 0.0
            for lo, hi in zip(bounds[:-1], bounds[1:]):
                for i in range(lo, hi):
                    for j in range(lo, hi):
                        loss = loss + jnp.where(
                            rel_j[i] > rel_j[j],
                            jnp.logaddexp(0.0, -(s[i] - s[j])), 0.0)
            return loss

        g, h = obj.grad_hess(pred, rel_j, table)
        g_ref = jax.grad(total_loss)(pred)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=1e-5, atol=1e-6)
        h_ref = jnp.diag(jax.hessian(total_loss)(pred))
        # h floors at 1e-16 for pairless docs; the oracle's true 0s
        # compare within atol
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                                   rtol=1e-4, atol=1e-5)
