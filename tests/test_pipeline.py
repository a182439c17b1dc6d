"""Pipeline parallelism tests (parallel/pipeline.py).

Oracle strategy: the pipelined program must match the UNPIPELINED same
math exactly — same loss trajectory, same per-parameter updates — on the
8-device CPU mesh (dp×pp), plus a generic pipeline_apply check against
sequential stage application.  SURVEY.md §2e lists PP absent upstream;
this is the beyond-parity row."""

import pytest
import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dmlc_core_tpu.parallel.pipeline import PipelineLM, pipeline_apply


def _mesh(dp, pp):
    devs = np.asarray(jax.devices()[: dp * pp]).reshape(dp, pp)
    return Mesh(devs, ("data", "pipe"))


class TestPipelineApply:
    def test_matches_sequential_stages(self, rng):
        """4 affine stages via the schedule == applying them in order."""
        pp, M, mb, d = 4, 3, 2, 8
        mesh = _mesh(1, pp)
        W = rng.normal(size=(pp, d, d)).astype(np.float32) * 0.3
        x = rng.normal(size=(M, mb, d)).astype(np.float32)

        def stage_fn(w, h):
            return jnp.tanh(h @ w[0])

        def run(w_all, xm):
            return pipeline_apply(stage_fn, w_all, xm, "pipe")

        out = jax.jit(shard_map(
            run, mesh=mesh, in_specs=(P("pipe"), P()), out_specs=P(),
            check_vma=False))(jnp.asarray(W), jnp.asarray(x))
        want = x
        for s in range(pp):
            want = np.tanh(want @ W[s])
        np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5,
                                   atol=2e-6)

    def test_gradients_match_sequential(self, rng):
        pp, M, mb, d = 2, 2, 2, 6
        mesh = _mesh(1, pp)
        W = rng.normal(size=(pp, d, d)).astype(np.float32) * 0.3
        x = rng.normal(size=(M, mb, d)).astype(np.float32)

        def stage_fn(w, h):
            return jnp.tanh(h @ w[0])

        def piped_loss(w_all, xm):
            y = pipeline_apply(stage_fn, w_all, xm, "pipe")
            return lax.psum(jnp.sum(y ** 2), "pipe") / pp

        gp = jax.jit(shard_map(
            jax.grad(piped_loss), mesh=mesh, in_specs=(P("pipe"), P()),
            out_specs=P("pipe"), check_vma=False))(jnp.asarray(W),
                                                   jnp.asarray(x))

        def seq_loss(w_all, xm):
            y = xm
            for s in range(pp):
                y = jnp.tanh(y @ w_all[s])
            return jnp.sum(y ** 2)

        gs = jax.grad(seq_loss)(jnp.asarray(W), jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gs),
                                   rtol=1e-4, atol=1e-6)


class TestPipelineLM:
    KW = dict(n_layers=4, d_model=32, n_heads=2, d_ff=64,
              vocab_size=64, max_len=16, n_micro=4)

    def _data(self, rng, B=8, S=16, V=64):
        return (rng.integers(0, V, size=(B, S)).astype(np.int32),
                rng.integers(0, V, size=(B, S)).astype(np.int32),
                np.ones((B, S), np.float32))

    @pytest.mark.slow
    def test_matches_unpipelined_exactly(self, rng):
        tokens, labels, mask = self._data(rng)
        m1 = PipelineLM(mesh=_mesh(2, 4), **self.KW)
        m1.init_params(0)
        m0 = PipelineLM(mesh=Mesh(np.asarray(jax.devices()[:1]).reshape(1),
                                  ("data",)), **self.KW)
        m0.init_params(0)
        for _ in range(3):
            l1 = m1.train_step(tokens, labels, mask)
            l0 = m0.train_step(tokens, labels, mask)
            assert abs(l1 - l0) < 1e-4, (l1, l0)
        # per-parameter states stay in lockstep too
        for k in m1.params:
            np.testing.assert_allclose(np.asarray(m1.params[k]),
                                       np.asarray(m0.params[k]),
                                       rtol=1e-4, atol=1e-5)

    @pytest.mark.slow
    def test_learns(self, rng):
        tokens, labels, mask = self._data(rng)
        m = PipelineLM(mesh=_mesh(2, 2), learning_rate=0.05, **self.KW)
        m.init_params(1)
        losses = [m.train_step(tokens, labels, mask) for _ in range(8)]
        assert losses[-1] < losses[0] - 0.1, losses

    @pytest.mark.slow
    def test_save_load_roundtrip_across_pipe_widths(self, rng, tmp_path):
        """A checkpoint written from a pipelined mesh must load onto a
        plain data mesh (pipe-sharded slabs gather on save) and keep the
        exact loss trajectory."""
        tokens, labels, mask = self._data(rng)
        m = PipelineLM(mesh=_mesh(2, 4), **self.KW)
        m.init_params(2)
        m.train_step(tokens, labels, mask)
        uri = str(tmp_path / "plm.ckpt")
        m.save_model(uri)
        m2 = PipelineLM.load_model(
            uri, mesh=Mesh(np.asarray(jax.devices()[:2]).reshape(2),
                           ("data",)))
        l_orig = m.train_step(tokens, labels, mask)
        l_load = m2.train_step(tokens, labels, mask)
        np.testing.assert_allclose(l_load, l_orig, rtol=1e-4)

    @pytest.mark.slow
    def test_fit_chunked_matches_per_step(self, rng):
        """The scan-chunked program (bench path) must reproduce
        the per-step trajectory exactly on the pipelined mesh."""
        tokens, labels, mask = self._data(rng)
        mesh = _mesh(2, 2)
        m1 = PipelineLM(mesh=mesh, **self.KW)
        m1.init_params(4)
        per_step = [m1.train_step(tokens, labels, mask) for _ in range(4)]
        m2 = PipelineLM(mesh=mesh, **self.KW)
        m2.init_params(4)
        fn = m2._make_multi(4)
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P("data"))
        t = jax.device_put(np.asarray(tokens, np.int32), sh)
        y = jax.device_put(np.asarray(labels, np.int32), sh)
        mk = jax.device_put(np.asarray(mask, np.float32), sh)
        _, losses = fn(m2.params, t, y, mk)
        np.testing.assert_allclose(np.asarray(losses), per_step, rtol=1e-5)
        # public wrapper: bookkeeping + finiteness
        m3 = PipelineLM(mesh=mesh, **self.KW)
        m3.init_params(4)
        loss, secs, chunk_times = m3.fit_chunked(
            tokens, labels, mask, n_steps=4, chunk=2)
        assert np.isfinite(loss) and secs > 0
        assert chunk_times[-1][0] == 4
