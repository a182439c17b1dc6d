"""Expert-parallel MoE tests (parallel/moe.py).

Oracles: the sharded all_to_all dispatch must equal a dense per-token
loop applying each token's expert (exact when capacity is loose); the
capacity rule must drop overflow tokens to zero; gradients must flow
(a toy routing problem learns).  SURVEY.md §2e lists EP absent upstream;
this is the beyond-parity row."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dmlc_core_tpu.parallel.moe import moe_ffn, reference_moe_ffn


def _weights(rng, E, D, F):
    return (rng.normal(size=(D, E)).astype(np.float32) * 0.5,
            rng.normal(size=(E, D, F)).astype(np.float32) * 0.2,
            np.zeros((E, F), np.float32),
            rng.normal(size=(E, F, D)).astype(np.float32) * 0.2,
            np.zeros((E, D), np.float32))


def _run_sharded(x, wr, w1, b1, w2, b2, ep, cf):
    mesh = Mesh(np.asarray(jax.devices()[:ep]).reshape(ep), ("expert",))

    def fn(x, wr, w1, b1, w2, b2):
        y, aux = moe_ffn(x, wr, w1, b1, w2, b2, "expert", cf)
        return y, lax.pmean(aux, "expert")

    return jax.jit(shard_map(
        fn, mesh=mesh,
        in_specs=(P(), P(), P("expert"), P("expert"), P("expert"),
                  P("expert")),
        out_specs=(P(), P()), check_vma=False))(
        jnp.asarray(x), jnp.asarray(wr), jnp.asarray(w1),
        jnp.asarray(b1), jnp.asarray(w2), jnp.asarray(b2))


class TestMoE:
    @pytest.mark.parametrize("ep", [2, 4])
    def test_matches_dense_oracle(self, rng, ep):
        T, D, F, E = 32, 8, 16, 8
        x = rng.normal(size=(T, D)).astype(np.float32)
        wr, w1, b1, w2, b2 = _weights(rng, E, D, F)
        y, aux = _run_sharded(x, wr, w1, b1, w2, b2, ep, cf=100.0)
        want = reference_moe_ffn(x, wr, w1, b1, w2, b2)
        np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4,
                                   atol=1e-5)
        assert float(aux) > 0

    def test_unsharded_matches_oracle(self, rng):
        T, D, F, E = 24, 6, 12, 4
        x = rng.normal(size=(T, D)).astype(np.float32)
        wr, w1, b1, w2, b2 = _weights(rng, E, D, F)
        y, _ = moe_ffn(jnp.asarray(x), jnp.asarray(wr), jnp.asarray(w1),
                       jnp.asarray(b1), jnp.asarray(w2), jnp.asarray(b2),
                       axis=None, capacity_factor=100.0)
        want = reference_moe_ffn(x, wr, w1, b1, w2, b2)
        np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4,
                                   atol=1e-5)

    def test_capacity_drops_match_oracle(self, rng):
        # route EVERYTHING to expert 0 via a biased router: with
        # cf·T/E = 2 slots, all but 2 tokens must drop to exactly zero
        T, D, F, E = 16, 4, 8, 4
        x = np.abs(rng.normal(size=(T, D))).astype(np.float32)
        wr, w1, b1, w2, b2 = _weights(rng, E, D, F)
        wr = np.zeros_like(wr)
        wr[:, 0] = 1.0                      # expert 0 wins every token
        cf = 0.5                            # cap = ceil(0.5·16/4) = 2
        y, _ = moe_ffn(jnp.asarray(x), jnp.asarray(wr), jnp.asarray(w1),
                       jnp.asarray(b1), jnp.asarray(w2), jnp.asarray(b2),
                       axis=None, capacity_factor=cf)
        want = reference_moe_ffn(x, wr, w1, b1, w2, b2, capacity_factor=cf)
        np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4,
                                   atol=1e-5)
        assert np.all(np.asarray(y)[2:] == 0)     # dropped → zeros
        assert np.any(np.asarray(y)[:2] != 0)

    def test_gradients_flow_and_learn(self, rng):
        # toy: tokens in 2 clusters, target = cluster-specific linear
        # map; a 2-expert MoE must beat its starting loss by a lot
        T, D, F, E, ep = 32, 4, 8, 2, 2
        mesh = Mesh(np.asarray(jax.devices()[:ep]).reshape(ep), ("expert",))
        x = rng.normal(size=(T, D)).astype(np.float32)
        x[: T // 2] += 3.0
        A0 = rng.normal(size=(D, D)).astype(np.float32)
        A1 = -A0
        target = np.concatenate([x[: T // 2] @ A0, x[T // 2:] @ A1])
        params = dict(zip("rabcd", (
            jnp.asarray(rng.normal(size=(D, E)).astype(np.float32) * 0.1),
            jnp.asarray(rng.normal(size=(E, D, F)).astype(np.float32) * 0.3),
            jnp.zeros((E, F)),
            jnp.asarray(rng.normal(size=(E, F, D)).astype(np.float32) * 0.3),
            jnp.zeros((E, D)))))

        def loss_fn(ps, x, t):
            y, aux = moe_ffn(x, ps["r"], ps["a"], ps["b"], ps["c"],
                             ps["d"], "expert", 4.0)
            return jnp.mean((y - t) ** 2) + 0.01 * aux

        step = jax.jit(shard_map(
            lambda ps, x, t: jax.tree.map(
                lambda p, g: p - 0.05 * g, ps,
                jax.grad(lambda q: lax.pmean(loss_fn(q, x, t), "expert")
                         )(ps)),
            mesh=mesh,
            in_specs=({"r": P(), "a": P("expert"), "b": P("expert"),
                       "c": P("expert"), "d": P("expert")}, P(), P()),
            out_specs={"r": P(), "a": P("expert"), "b": P("expert"),
                       "c": P("expert"), "d": P("expert")},
            check_vma=False))

        eval_loss = jax.jit(shard_map(
            lambda ps, x, t: lax.pmean(loss_fn(ps, x, t), "expert"),
            mesh=mesh,
            in_specs=({"r": P(), "a": P("expert"), "b": P("expert"),
                       "c": P("expert"), "d": P("expert")}, P(), P()),
            out_specs=P(), check_vma=False))
        xj, tj = jnp.asarray(x), jnp.asarray(target)
        first = last = None
        for _ in range(60):
            cur = float(eval_loss(params, xj, tj))
            first = cur if first is None else first
            last = cur
            params = step(params, xj, tj)
        assert last < first * 0.5, (first, last)
