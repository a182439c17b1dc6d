"""Tests for ring attention and the BERT dp×tp×sp trainer (config 4).

Oracles: single-device full-softmax attention; sharded-equals-replicated
training (the tp/sp/dp correctness check); KVStore dist_sync vs fused
psum equivalence on the first step."""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dmlc_core_tpu.models.bert import BERT
from dmlc_core_tpu.parallel.mesh import MeshSpec, create_mesh, local_mesh
from dmlc_core_tpu.parallel.ring_attention import (
    reference_attention, ring_attention)

TINY = dict(n_layers=2, d_model=32, n_heads=4, d_ff=64, vocab_size=64,
            max_len=32, learning_rate=0.1)


def _batch(B=4, S=32, V=64, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, V, size=(B, S))
    mask = (rng.uniform(size=(B, S)) < 0.3).astype(np.float32)
    mask[:, 0] = 1.0  # never fully unmasked
    return tokens, tokens.copy(), mask


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("n_seq", [2, 4, 8])
    def test_matches_full_softmax(self, causal, n_seq, rng):
        mesh = Mesh(np.asarray(jax.devices()[:n_seq]), ("seq",))
        B, S, H, D = 2, 8 * n_seq, 3, 8
        q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
                   for _ in range(3))
        f = jax.jit(shard_map(
            partial(ring_attention, axis_name="seq", causal=causal),
            mesh=mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"), check_vma=False))
        out = np.asarray(f(q, k, v))
        ref = np.asarray(reference_attention(q, k, v, causal=causal))
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_single_device_axis(self, rng):
        # size-1 seq axis: ring degenerates to local attention
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("seq",))
        q = jnp.asarray(rng.normal(size=(1, 8, 2, 4)).astype(np.float32))
        f = jax.jit(shard_map(partial(ring_attention, axis_name="seq"),
                              mesh=mesh, in_specs=(P(None, "seq"),) * 3,
                              out_specs=P(None, "seq"), check_vma=False))
        np.testing.assert_allclose(np.asarray(f(q, q, q)),
                                   np.asarray(reference_attention(q, q, q)),
                                   atol=2e-5)


class TestBERT:
    def test_trains_and_loss_decreases(self):
        mesh = create_mesh(MeshSpec(data=2, model=2, seq=2))
        m = BERT(mesh=mesh, **TINY)
        m.init_params(0)
        tokens, labels, mask = _batch()
        losses = [m.train_step(tokens, labels, mask) for _ in range(10)]
        assert losses[-1] < losses[0]

    @pytest.mark.slow
    def test_sharded_equals_replicated(self):
        """THE tp/sp/dp oracle: an 8-way (2,2,2) mesh must reproduce the
        1-device loss trajectory (bf16 tolerance)."""
        tokens, labels, mask = _batch(seed=5)
        trajs = []
        for mesh in (create_mesh(MeshSpec(data=2, model=2, seq=2)),
                     local_mesh(1)):
            m = BERT(mesh=mesh, **TINY)
            m.init_params(7)
            trajs.append([m.train_step(tokens, labels, mask) for _ in range(4)])
        np.testing.assert_allclose(trajs[0], trajs[1], rtol=2e-2)

    @pytest.mark.slow
    def test_fit_chunked_matches_per_step(self):
        """The scan-chunked multi-step program (fit_chunked, the
        bench path) must reproduce the per-step train_step
        trajectory exactly: same batch, same 4 steps, same final loss."""
        tokens, labels, mask = _batch(seed=9)
        mesh = create_mesh(MeshSpec(data=2, model=2, seq=2))
        m1 = BERT(mesh=mesh, **TINY)
        m1.init_params(3)
        per_step = [m1.train_step(tokens, labels, mask) for _ in range(4)]
        m2 = BERT(mesh=mesh, **TINY)
        m2.init_params(3)
        loss, secs, chunk_times = m2.fit_chunked(
            tokens, labels, mask, n_steps=4, chunk=2, warmup_chunks=0)
        # warmup_chunks=0 still runs one warm chunk (compile); with
        # chunk=2 the timed region then covers steps 3-6 of the model's
        # life... so compare trajectories by rebuilding: a fresh model
        # with warmup disabled isn't possible — instead check the FIRST
        # chunk's losses against per_step directly via a third model.
        m3 = BERT(mesh=mesh, **TINY)
        m3.init_params(3)
        fn = m3._make_multi(4)
        import jax as _jax
        from jax.sharding import NamedSharding as _NS
        sh = _NS(mesh, P("data", "seq"))
        t = _jax.device_put(np.asarray(tokens, np.int32), sh)
        y = _jax.device_put(np.asarray(labels, np.int32), sh)
        mk = _jax.device_put(np.asarray(mask, np.float32), sh)
        _, _, losses = fn(m3.params, m3.opt_state, t, y, mk)
        np.testing.assert_allclose(np.asarray(losses), per_step, rtol=1e-5)
        assert np.isfinite(loss)
        assert secs > 0
        assert chunk_times[-1][0] == 4      # all steps accounted for

    @pytest.mark.slow
    def test_save_load_roundtrip(self, tmp_path):
        """Checkpoint (Stream/serializer layer) must restore params AND
        momentum so a resumed model continues the exact trajectory."""
        tokens, labels, mask = _batch(seed=11)
        mesh = create_mesh(MeshSpec(data=2, model=2, seq=2))
        m = BERT(mesh=mesh, **TINY)
        m.init_params(5)
        m.train_step(tokens, labels, mask)     # non-zero momentum
        uri = str(tmp_path / "bert.ckpt")
        m.save_model(uri)
        m2 = BERT.load_model(uri, mesh=mesh)
        l_orig = m.train_step(tokens, labels, mask)
        l_load = m2.train_step(tokens, labels, mask)
        np.testing.assert_allclose(l_load, l_orig, rtol=1e-6)
        # wrong-magic file fails loudly
        from dmlc_core_tpu.base.logging import Error
        from dmlc_core_tpu.parallel.pipeline import PipelineLM
        with pytest.raises(Error, match="magic"):
            PipelineLM.load_model(uri)

    @pytest.mark.slow
    def test_kvstore_first_step_matches_fused(self):
        mesh = create_mesh(MeshSpec(data=4, seq=2))
        tokens, labels, mask = _batch(seed=2)
        lf = BERT(mesh=mesh, grad_sync="fused", **TINY)
        lf.init_params(3)
        lk = BERT(mesh=mesh, grad_sync="kvstore", **TINY)
        lk.init_params(3)
        # loss is computed before the update → step-0 losses match exactly
        assert lf.train_step(tokens, labels, mask) == pytest.approx(
            lk.train_step(tokens, labels, mask), rel=1e-5)
        # and the *second* losses agree too (kvstore = plain SGD vs fused
        # SGD-momentum: first update identical, so second loss matches)
        assert lf.train_step(tokens, labels, mask) == pytest.approx(
            lk.train_step(tokens, labels, mask), rel=2e-2)

    def test_head_divisibility_validated(self):
        from dmlc_core_tpu.base.logging import Error

        mesh = create_mesh(MeshSpec(data=2, model=4))
        with pytest.raises(Error):
            BERT(mesh=mesh, n_layers=1, d_model=24, n_heads=6, d_ff=32,
                 vocab_size=32, max_len=16)


class TestBERTMoE:
    """ffn_type='moe': expert-parallel Switch FFN inside the BERT stack.

    Oracle: a dp×ep mesh must track the unsharded single-device run
    exactly (same params, same tokens — the all_to_all dispatch and the
    expert-axis grad bookkeeping must not change the math)."""

    KW = dict(n_layers=2, d_model=32, n_heads=4, d_ff=64, vocab_size=64,
              max_len=16, learning_rate=0.1, ffn_type="moe", n_experts=4,
              capacity_factor=8.0)

    @pytest.mark.parametrize("partial_mask", [False, True])
    @pytest.mark.slow
    def test_ep_matches_unsharded(self, partial_mask):
        """dp×ep must track the unsharded run exactly — including under
        PARTIAL masks, where the aux must weight routing stats by tokens
        routed, not loss positions (it is computed from globally psummed
        stats).  Capacity is loose here: the drop RULE is per dispatch
        group by design (see test_capacity_pressure_sharded)."""
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, 64, size=(8, 16)).astype(np.int32)
        if partial_mask:
            # skewed density: first half of the batch mostly masked-in,
            # second half mostly masked-out — exactly the case where a
            # mask-weighted LOCAL aux diverges from the global aux
            mask = (rng.random((8, 16)) <
                    np.linspace(0.9, 0.1, 8)[:, None]).astype(np.float32)
        else:
            mask = np.ones((8, 16), np.float32)
        mesh = create_mesh(MeshSpec(data=2, expert=2),
                           devices=jax.devices()[:4])
        m1 = BERT(mesh=mesh, **self.KW)
        m1.init_params(0)
        m0 = BERT(mesh=Mesh(np.asarray(jax.devices()[:1]), ("data",)),
                  **self.KW)
        m0.init_params(0)
        losses = []
        for _ in range(4):
            l1 = m1.train_step(tokens, tokens.copy(), mask)
            l0 = m0.train_step(tokens, tokens.copy(), mask)
            assert abs(l1 - l0) < 2e-4, (l1, l0)
            losses.append(l1)
        if not partial_mask:
            assert losses[-1] < losses[0] - 0.1   # and it learns

    @pytest.mark.slow
    def test_capacity_pressure_sharded(self):
        """Under capacity pressure exact sharded/unsharded parity is NOT
        a contract: capacity binds per dispatch group (each token shard
        keeps its first cap-per-expert tokens — standard Switch), so the
        surviving sets differ.  The contract is: training stays finite
        and learns."""
        rng = np.random.default_rng(1)
        tokens = rng.integers(0, 64, size=(8, 16)).astype(np.int32)
        mask = np.ones((8, 16), np.float32)
        mesh = create_mesh(MeshSpec(data=2, expert=2),
                           devices=jax.devices()[:4])
        m = BERT(mesh=mesh, **{**self.KW, "capacity_factor": 1.0})
        m.init_params(0)
        losses = [m.train_step(tokens, tokens.copy(), mask)
                  for _ in range(6)]
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0] - 0.05, losses

    def test_moe_requires_fused_sync(self):
        from dmlc_core_tpu.base.logging import Error
        with pytest.raises(Error):
            BERT(grad_sync="kvstore", **{**self.KW, "learning_rate": 0.1})


class TestUlysses:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_softmax(self, causal, rng):
        from functools import partial

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from dmlc_core_tpu.parallel.mesh import MeshSpec, create_mesh
        from dmlc_core_tpu.parallel.ulysses import ulysses_attention
        from dmlc_core_tpu.parallel.ring_attention import reference_attention

        B, S, H, D = 2, 64, 8, 16
        mesh = create_mesh(MeshSpec(seq=8))
        q = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))

        fn = shard_map(
            partial(ulysses_attention, axis_name="seq", causal=causal),
            mesh=mesh,
            in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
            out_specs=P(None, "seq"),
            check_vma=False,
        )
        out = np.asarray(jax.jit(fn)(q, k, v))
        want = np.asarray(reference_attention(q, k, v, causal=causal))
        np.testing.assert_allclose(out, want, atol=2e-5, rtol=1e-4)

    def test_head_divisibility_rejected(self, rng):
        from functools import partial

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from dmlc_core_tpu.parallel.mesh import MeshSpec, create_mesh
        from dmlc_core_tpu.parallel.ulysses import ulysses_attention

        mesh = create_mesh(MeshSpec(seq=8))
        x = jnp.zeros((1, 64, 6, 8))       # 6 heads, 8 devices
        fn = shard_map(
            partial(ulysses_attention, axis_name="seq"),
            mesh=mesh,
            in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"),
            check_vma=False,
        )
        with pytest.raises(ValueError, match="not divisible"):
            jax.jit(fn)(x, x, x)

    def test_matches_ring(self, rng):
        """Both SP formulations must agree on the same sharded inputs."""
        from functools import partial

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from dmlc_core_tpu.parallel.mesh import MeshSpec, create_mesh
        from dmlc_core_tpu.parallel.ring_attention import ring_attention
        from dmlc_core_tpu.parallel.ulysses import ulysses_attention

        B, S, H, D = 1, 32, 8, 8
        mesh = create_mesh(MeshSpec(seq=4))
        q = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))

        def mk(fn):
            return jax.jit(shard_map(
                partial(fn, axis_name="seq", causal=True), mesh=mesh,
                in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"),
                check_vma=False))

        out_u = np.asarray(mk(ulysses_attention)(q, k, v))
        out_r = np.asarray(mk(ring_attention)(q, k, v))
        np.testing.assert_allclose(out_u, out_r, atol=2e-5, rtol=1e-4)

    def test_bert_trains_with_ulysses(self):
        from dmlc_core_tpu.models.bert import BERT
        from dmlc_core_tpu.parallel.mesh import MeshSpec, create_mesh

        mesh = create_mesh(MeshSpec(data=2, model=2, seq=2))
        bert = BERT(n_layers=2, d_model=32, n_heads=4, d_ff=64,
                    vocab_size=64, max_len=32, learning_rate=0.1,
                    sp_method="ulysses", mesh=mesh)
        bert.init_params(0)
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, 64, size=(4, 16))
        mask = np.ones((4, 16), np.float32)
        losses = [bert.train_step(tokens, tokens.copy(), mask)
                  for _ in range(8)]
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]      # actually learns

    @pytest.mark.slow
    def test_bert_ring_vs_ulysses_first_step(self):
        """Same init, same batch: the two SP methods must produce the same
        first-step loss (both are exact attention)."""
        from dmlc_core_tpu.models.bert import BERT
        from dmlc_core_tpu.parallel.mesh import MeshSpec, create_mesh

        rng = np.random.default_rng(1)
        tokens = rng.integers(0, 64, size=(2, 16))
        mask = np.ones((2, 16), np.float32)
        losses = {}
        for method in ("ring", "ulysses"):
            mesh = create_mesh(MeshSpec(seq=4))
            b = BERT(n_layers=1, d_model=16, n_heads=4, d_ff=32,
                     vocab_size=64, max_len=32, sp_method=method, mesh=mesh)
            b.init_params(7)
            losses[method] = b.train_step(tokens, tokens.copy(), mask)
        np.testing.assert_allclose(losses["ring"], losses["ulysses"],
                                   rtol=2e-4)

    def test_ulysses_head_check_at_construction(self):
        from dmlc_core_tpu.base.logging import Error
        from dmlc_core_tpu.models.bert import BERT
        from dmlc_core_tpu.parallel.mesh import MeshSpec, create_mesh

        mesh = create_mesh(MeshSpec(model=2, seq=4))
        with pytest.raises(Error, match="n_heads=6"):
            BERT(n_layers=1, d_model=24, n_heads=6, d_ff=32, vocab_size=32,
                 max_len=16, sp_method="ulysses", mesh=mesh)


class TestLocalAttention:
    def test_dispatch_and_correctness_cpu(self, rng):
        from dmlc_core_tpu.ops.attention import flash_eligible, local_attention
        from dmlc_core_tpu.parallel.ring_attention import reference_attention

        # CPU: never flash-eligible; dense path must be exact
        if jax.default_backend() != "tpu":
            assert not flash_eligible(2, 512, 4, 64)
        q = jnp.asarray(rng.normal(size=(2, 64, 4, 16)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(2, 64, 4, 16)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(2, 64, 4, 16)).astype(np.float32))
        out = np.asarray(local_attention(q, k, v, causal=True))
        want = np.asarray(reference_attention(q, k, v, causal=True))
        np.testing.assert_allclose(out, want, atol=1e-6)

    def test_eligibility_rules(self):
        from dmlc_core_tpu.ops.attention import flash_eligible
        import jax

        if jax.default_backend() != "tpu":
            pytest.skip("flash eligibility rules are TPU-only")
        assert flash_eligible(2, 512, 4, 64)
        assert not flash_eligible(2, 200, 4, 64)    # seq not /128
        assert not flash_eligible(2, 128, 4, 64)    # too short
        assert not flash_eligible(2, 512, 4, 32)    # head_dim too small
