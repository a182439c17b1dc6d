"""BERT encoder trained with explicit mesh parallelism (config 4).

The transformer consumer of the substrate, exercising the three mesh axes
the GBT family doesn't:

* ``data`` — batch sharded; gradient sync either **fused** (in-step
  ``psum`` — one XLA AllReduce riding ICI/DCN, the performance path) or
  through the **KVStore** ``dist_sync`` API (per-worker gradients pushed/
  pulled between steps — MXNet-parity semantics, BASELINE config 4's
  "KVStore dist_sync gradient allreduce").
* ``model`` — Megatron-style tensor parallelism: attention heads and the
  MLP hidden dimension sharded; row-parallel projections follow with a
  ``psum`` over ``model``; embedding/LayerNorm/head grads are psummed
  over ``model`` because those weights are replicated across it.
* ``seq`` — sequence/context parallelism: tokens sharded, exact attention
  via :func:`~dmlc_core_tpu.parallel.ring_attention.ring_attention`
  (K/V blocks rotating over the ICI ring) — long-context first-class.

The whole train step is ONE ``shard_map`` program, so every collective is
explicit and auditable — this is the XLA re-founding of the reference's
distributed story (rabit tree allreduce + PS bootstrap, SURVEY.md §2c/§5),
where the communication backend is the compiler's collectives, not
sockets.  bf16 compute, f32 master weights and reductions.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_core_tpu.base.logging import CHECK, CHECK_EQ
from dmlc_core_tpu.base.parameter import Parameter, field
from dmlc_core_tpu.base.timer import get_time
from dmlc_core_tpu.parallel.collectives import replicate_fwd_psum_bwd
from dmlc_core_tpu.parallel.kvstore import KVStore
from dmlc_core_tpu.parallel.mesh import local_mesh
from dmlc_core_tpu.ops.attention import local_attention
from dmlc_core_tpu.parallel.ring_attention import ring_attention
from dmlc_core_tpu.parallel.moe import moe_ffn
from dmlc_core_tpu.parallel.ulysses import ulysses_attention

__all__ = ["BERT", "BERTParam"]


class BERTParam(Parameter):
    """BERT-base defaults (L12 / d768 / h12 / ff3072)."""

    n_layers = field(int, default=12, lower_bound=1)
    d_model = field(int, default=768, lower_bound=8)
    n_heads = field(int, default=12, lower_bound=1)
    d_ff = field(int, default=3072, lower_bound=8)
    vocab_size = field(int, default=30522, lower_bound=16)
    max_len = field(int, default=512, lower_bound=8)
    learning_rate = field(float, default=1e-3, lower_bound=0.0)
    grad_sync = field(str, default="fused", enum=["fused", "kvstore"],
                      description="in-step psum vs KVStore dist_sync")
    sp_method = field(str, default="ring", enum=["ring", "ulysses"],
                      description="sequence-parallel attention: K/V ring "
                                  "rotation vs all-to-all head scatter")
    ffn_type = field(str, default="dense", enum=["dense", "moe"],
                     description="dense FFN vs Switch-style top-1 MoE "
                                 "(experts shard over the 'expert' axis)")
    n_experts = field(int, default=8, lower_bound=2,
                      description="experts per MoE layer")
    capacity_factor = field(float, default=1.25, lower_bound=0.1)
    moe_aux_weight = field(float, default=0.01, lower_bound=0.0,
                           description="load-balance aux loss coefficient")


def _norm(x, gamma, beta, eps=1e-6):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + eps) * gamma + beta).astype(x.dtype)


class BERT:
    """Masked-LM trainer over a (data, model, seq) mesh.

    Parameters live as replicated-or-model-sharded global ``jax.Array``s;
    the step is jitted once and reused every round.
    """

    def __init__(self, param: Optional[BERTParam] = None,
                 mesh: Optional[Mesh] = None, **kwargs: Any):
        self.param = param or BERTParam()
        if kwargs:
            self.param.init(kwargs)
        self.mesh = mesh if mesh is not None else local_mesh()
        names = self.mesh.axis_names
        for ax in ("data",):
            CHECK(ax in names, f"mesh needs a {ax!r} axis")
        # axis presence (not size): a size-1 named axis still binds inside
        # shard_map, so psum/ppermute over it are legal no-ops; an absent
        # axis must not be referenced at all
        self._has_model = "model" in names
        self._has_seq = "seq" in names
        self._tp = self.mesh.shape.get("model", 1)
        self._sp = self.mesh.shape.get("seq", 1)
        self._dp = self.mesh.shape.get("data", 1)
        self._ep = self.mesh.shape.get("expert", 1)
        self._has_expert = "expert" in names and self._ep > 1
        p = self.param
        self._moe = p.ffn_type == "moe"
        # MoE shards the batch over data×expert (the expert axis doubles
        # as extra batch parallelism outside the expert dispatch); a
        # single definition feeds the input sharding, the step's psum
        # axes, and the grad sync so they can never disagree
        self._batch_axes = (("data", "expert")
                            if self._moe and self._has_expert
                            else ("data",))
        if self._moe:
            CHECK(p.grad_sync == "fused",
                  "ffn_type='moe' supports grad_sync='fused' only")
            if self._has_expert:
                CHECK_EQ(p.n_experts % self._ep, 0, "n_experts % ep != 0")
        CHECK_EQ(p.n_heads % max(self._tp, 1), 0, "n_heads % tp != 0")
        CHECK_EQ(p.d_ff % max(self._tp, 1), 0, "d_ff % tp != 0")
        if p.sp_method == "ulysses" and self._has_seq:
            # fail at construction with the USER's numbers — inside
            # shard_map the error would report shard-local head counts
            CHECK_EQ((p.n_heads // max(self._tp, 1)) % max(self._sp, 1), 0,
                     f"ulysses needs (n_heads/tp) % sp == 0 "
                     f"(n_heads={p.n_heads}, tp={self._tp}, sp={self._sp})")
        self.params: Optional[Dict[str, jax.Array]] = None
        self.opt_state: Optional[Dict[str, jax.Array]] = None
        self._step_fn: Optional[Callable] = None
        self._kv: Optional[KVStore] = None

    # -- parameter construction ----------------------------------------
    def _param_specs(self) -> Dict[str, P]:
        p = self.param
        mdl = "model" if self._has_model else None
        specs: Dict[str, P] = {
            "embed": P(),              # [V, D] replicated (grads psum over model)
            "pos": P(),                # [max_len, D]
            "lm_head": P(),            # [D, V]
            "ln_f.g": P(), "ln_f.b": P(),
        }
        for i in range(p.n_layers):
            specs[f"l{i}.ln1.g"] = P()
            specs[f"l{i}.ln1.b"] = P()
            specs[f"l{i}.ln2.g"] = P()
            specs[f"l{i}.ln2.b"] = P()
            specs[f"l{i}.wqkv"] = P(None, None, mdl, None)      # [3, D, H, Dh]
            specs[f"l{i}.wo"] = P(mdl, None, None)              # [H, Dh, D]
            if self._moe:
                exp = "expert" if self._has_expert else None
                specs[f"l{i}.wre"] = P()                        # [D, E] router
                specs[f"l{i}.we1"] = P(exp)                     # [E, D, F]
                specs[f"l{i}.be1"] = P(exp)                     # [E, F]
                specs[f"l{i}.we2"] = P(exp)                     # [E, F, D]
                specs[f"l{i}.be2"] = P(exp)                     # [E, D]
            else:
                specs[f"l{i}.w1"] = P(None, mdl)                # [D, F]
                specs[f"l{i}.b1"] = P(mdl)                      # [F]
                specs[f"l{i}.w2"] = P(mdl, None)                # [F, D]
                specs[f"l{i}.b2"] = P()                         # [D]
        return specs

    def init_params(self, seed: int = 0) -> None:
        p = self.param
        rng = np.random.default_rng(seed)
        dh = p.d_model // p.n_heads

        def g(*shape, scale=0.02):
            return (rng.normal(size=shape) * scale).astype(np.float32)

        host: Dict[str, np.ndarray] = {
            "embed": g(p.vocab_size, p.d_model),
            "pos": g(p.max_len, p.d_model),
            "lm_head": g(p.d_model, p.vocab_size),
            "ln_f.g": np.ones(p.d_model, np.float32),
            "ln_f.b": np.zeros(p.d_model, np.float32),
        }
        for i in range(p.n_layers):
            host[f"l{i}.ln1.g"] = np.ones(p.d_model, np.float32)
            host[f"l{i}.ln1.b"] = np.zeros(p.d_model, np.float32)
            host[f"l{i}.ln2.g"] = np.ones(p.d_model, np.float32)
            host[f"l{i}.ln2.b"] = np.zeros(p.d_model, np.float32)
            host[f"l{i}.wqkv"] = g(3, p.d_model, p.n_heads, dh)
            host[f"l{i}.wo"] = g(p.n_heads, dh, p.d_model)
            if self._moe:
                E = p.n_experts
                host[f"l{i}.wre"] = g(p.d_model, E)
                host[f"l{i}.we1"] = g(E, p.d_model, p.d_ff)
                host[f"l{i}.be1"] = np.zeros((E, p.d_ff), np.float32)
                host[f"l{i}.we2"] = g(E, p.d_ff, p.d_model)
                host[f"l{i}.be2"] = np.zeros((E, p.d_model), np.float32)
            else:
                host[f"l{i}.w1"] = g(p.d_model, p.d_ff)
                host[f"l{i}.b1"] = np.zeros(p.d_ff, np.float32)
                host[f"l{i}.w2"] = g(p.d_ff, p.d_model)
                host[f"l{i}.b2"] = np.zeros(p.d_model, np.float32)
        specs = self._param_specs()
        self.params = {
            k: jax.device_put(v, NamedSharding(self.mesh, specs[k]))
            for k, v in host.items()
        }
        self.opt_state = {k: jnp.zeros_like(v) for k, v in self.params.items()}
        self._build_step()
        if p.grad_sync == "kvstore":
            self._kv = KVStore.create("dist_sync", learning_rate=p.learning_rate,
                                      mesh=self.mesh, axis="data")
            for k in self.params:
                self._kv.init(k, self.params[k])

    # -- checkpointing (Stream/serializer consumer layer) ---------------
    _MODEL_MAGIC = b"DMLCTPU.BERT.v1\n"

    def save_model(self, uri: str) -> None:
        """Serialize hyperparams + params + momentum to any Stream URI
        (SURVEY.md §5 checkpoint layering; see models/checkpoint.py)."""
        from dmlc_core_tpu.models.checkpoint import gather_tree, save_payload

        CHECK(self.params is not None, "save_model before init_params")
        save_payload(uri, self._MODEL_MAGIC, {
            "param": self.param.to_dict(),
            "params": gather_tree(self.params),
            "opt_state": gather_tree(self.opt_state),
        })

    @classmethod
    def load_model(cls, uri: str, mesh: Optional[Mesh] = None) -> "BERT":
        """Inverse of :meth:`save_model`: params re-shard onto ``mesh``
        via this model's own PartitionSpecs; training resumes exactly
        (momentum restored)."""
        from dmlc_core_tpu.models.checkpoint import load_payload

        payload = load_payload(uri, cls._MODEL_MAGIC)
        model = cls(mesh=mesh, **payload["param"])
        specs = model._param_specs()
        model.params = {
            k: jax.device_put(v, NamedSharding(model.mesh, specs[k]))
            for k, v in payload["params"].items()}
        model.opt_state = {
            k: jax.device_put(v, NamedSharding(model.mesh, specs[k]))
            for k, v in payload["opt_state"].items()}
        model._build_step()
        if model.param.grad_sync == "kvstore":
            model._kv = KVStore.create(
                "dist_sync", learning_rate=model.param.learning_rate,
                mesh=model.mesh, axis="data")
            for k in model.params:
                model._kv.init(k, model.params[k])
        return model

    # -- forward/backward under shard_map ------------------------------
    def _local_loss(self, params, tokens, labels, mask):
        """Per-device forward: tokens [b, s_local] → (loss_sum, n_tokens).

        Runs inside shard_map: arrays are local blocks; heads/ff local to
        the model shard; tokens local to the seq shard.
        """
        p = self.param
        sp_idx = lax.axis_index("seq") if self._has_seq else 0
        s_local = tokens.shape[1]
        pos0 = sp_idx * s_local
        x = (jnp.take(params["embed"], tokens, axis=0)
             + lax.dynamic_slice_in_dim(params["pos"], pos0, s_local, 0)[None])
        x = x.astype(jnp.bfloat16)

        aux_total = jnp.float32(0.0)

        def join_model(y):
            # Megatron g: psum forward (row-parallel join), identity backward
            return lax.psum(y, "model") if self._has_model else y

        def enter_model(y):
            # Megatron f: identity forward, psum backward — every shard then
            # holds COMPLETE grads for upstream replicated params
            return (replicate_fwd_psum_bwd(y, "model")
                    if self._has_model else y)

        for i in range(p.n_layers):
            h = _norm(x, params[f"l{i}.ln1.g"], params[f"l{i}.ln1.b"])
            h = enter_model(h)
            qkv = jnp.einsum("bsd,cdhk->cbshk", h.astype(jnp.float32),
                             params[f"l{i}.wqkv"]).astype(jnp.bfloat16)
            if self._has_seq:
                sp_attn = (ulysses_attention if p.sp_method == "ulysses"
                           else ring_attention)
                attn = sp_attn(qkv[0], qkv[1], qkv[2], axis_name="seq")
            else:
                attn = local_attention(qkv[0], qkv[1], qkv[2])
            o = jnp.einsum("bshk,hkd->bsd", attn.astype(jnp.float32),
                           params[f"l{i}.wo"])
            o = join_model(o)                              # row-parallel join
            x = x + o.astype(jnp.bfloat16)
            h = _norm(x, params[f"l{i}.ln2.g"], params[f"l{i}.ln2.b"])
            if self._moe:
                # Switch MoE FFN: runs OUTSIDE the model-parallel region
                # (replicated over 'model'; experts shard over 'expert')
                b, s_l, Dm = h.shape
                y, (a_sum, p_sum, t_cnt) = moe_ffn(
                    h.astype(jnp.float32).reshape(b * s_l, Dm),
                    params[f"l{i}.wre"], params[f"l{i}.we1"],
                    params[f"l{i}.be1"], params[f"l{i}.we2"],
                    params[f"l{i}.be2"],
                    axis="expert" if self._has_expert else None,
                    capacity_factor=p.capacity_factor, stats=True)
                # routing-statistic SUMS psum over every token-sharding
                # axis so the aux is computed from GLOBAL expert loads —
                # exact parity with the unsharded model (a mean of
                # per-shard aux values is a different statistic)
                tok_axes = self._batch_axes + (
                    ("seq",) if self._has_seq else ())
                a_sum = lax.psum(a_sum, tok_axes)
                p_sum = lax.psum(p_sum, tok_axes)
                t_glob = lax.psum(t_cnt, tok_axes)
                aux_total = aux_total + p.n_experts * jnp.sum(
                    (a_sum / t_glob) * (p_sum / t_glob))
                x = x + y.reshape(b, s_l, Dm).astype(jnp.bfloat16)
            else:
                h = enter_model(h)
                u = jax.nn.gelu(
                    jnp.einsum("bsd,df->bsf", h.astype(jnp.float32),
                               params[f"l{i}.w1"]) + params[f"l{i}.b1"])
                m = jnp.einsum("bsf,fd->bsd", u, params[f"l{i}.w2"])
                m = join_model(m) + params[f"l{i}.b2"]     # row-parallel join
                x = x + m.astype(jnp.bfloat16)
        x = _norm(x, params["ln_f.g"], params["ln_f.b"])
        logits = jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                            params["lm_head"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        tok_lp = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        mask_f = mask.astype(jnp.float32)
        loss_sum = -(tok_lp * mask_f).sum()
        if self._moe:
            # aux_total is GLOBAL (psummed stats) and identical on every
            # shard; weighting by the local mask sum makes the later
            # psum/n_glob normalization recover exactly aux_w · aux_total
            loss_sum = loss_sum + (p.moe_aux_weight
                                   * aux_total / p.n_layers * mask_f.sum())
        return loss_sum, mask_f.sum()

    def _build_step(self) -> None:
        p = self.param
        specs = self._param_specs()
        lr = p.learning_rate
        fused = p.grad_sync == "fused"
        has_seq = self._has_seq

        def psum_seq(x):
            return lax.psum(x, "seq") if has_seq else x

        batch_axes = self._batch_axes
        expert_keys = (".we1", ".be1", ".we2", ".be2")

        def step(params, opt_state, tokens, labels, mask):
            def loss_fn(ps):
                ls, n = self._local_loss(ps, tokens, labels, mask)
                return ls, n

            (loss_sum, n_tok), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            n_glob = psum_seq(lax.psum(n_tok, batch_axes))
            # normalize to global-mean-per-token gradients
            grads = jax.tree.map(lambda g: g / n_glob, grads)
            # intra-worker seq reduction (model grads are already complete
            # on every shard via the Megatron f/g boundary operators)
            grads = {k: psum_seq(g) for k, g in grads.items()}
            loss = psum_seq(lax.psum(loss_sum, batch_axes)) / n_glob
            if fused:
                # expert-sharded weights already accumulated their expert-
                # axis contributions through the all_to_all backward; a
                # psum over 'expert' would double-count them
                grads = {k: lax.psum(
                    g, "data" if k.endswith(expert_keys) else batch_axes)
                    for k, g in grads.items()}
                # SGD + momentum, f32 master weights
                new_opt = {k: 0.9 * opt_state[k] + grads[k] for k in grads}
                new_params = {k: params[k] - lr * new_opt[k] for k in grads}
                return new_params, new_opt, loss
            # kvstore mode: hand back per-data-worker grads, stacked on a
            # leading axis sharded over 'data' (the KVStore syncs them)
            stacked = {k: g[None] for k, g in grads.items()}
            return params, stacked, loss

        seq_ax = "seq" if self._has_seq else None
        batch_spec = P(batch_axes, seq_ax)
        in_specs = (
            {k: specs[k] for k in specs},
            {k: specs[k] for k in specs},
            batch_spec, batch_spec, batch_spec,
        )
        if fused:
            # scan-chunked multi-step program (fit_chunked): K optimizer
            # steps per dispatch.  A per-step host loop (train_step's
            # float(loss)) pays per-dispatch + fetch latency on every
            # ~50ms BERT-base step, the same trap the hist-GBT round
            # loop solved with lax.scan chunks.
            self._multi_cache: dict = {}

            def make_multi(K: int):
                if K not in self._multi_cache:
                    def multi(params, opt_state, tokens, labels, mask):
                        def body(carry, _):
                            ps, os_ = carry
                            p2, o2, loss = step(ps, os_, tokens, labels,
                                                mask)
                            return (p2, o2), loss

                        (p2, o2), losses = lax.scan(
                            body, (params, opt_state), None, length=K)
                        return p2, o2, losses

                    mapped_k = shard_map(
                        multi, mesh=self.mesh, in_specs=in_specs,
                        out_specs=({k: specs[k] for k in specs},
                                   {k: specs[k] for k in specs}, P()),
                        check_vma=False)
                    self._multi_cache[K] = jax.jit(
                        mapped_k, donate_argnums=(0, 1))
                return self._multi_cache[K]

            self._make_multi = make_multi
            out_specs = ({k: specs[k] for k in specs},
                         {k: specs[k] for k in specs}, P())
        else:
            gspecs = {k: P("data", *(specs[k] or ())) for k in specs}
            out_specs = ({k: specs[k] for k in specs}, gspecs, P())
        mapped = shard_map(step, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        donate = (0, 1) if fused else ()
        self._step_fn = jax.jit(mapped, donate_argnums=donate)

    # -- public API ----------------------------------------------------
    def train_step(self, tokens: np.ndarray, labels: np.ndarray,
                   mask: np.ndarray) -> float:
        """One masked-LM step on global [B, S] int32 batches."""
        CHECK(self.params is not None, "call init_params() first")
        # out-of-range S or token ids would be silently clamped/clipped by
        # dynamic_slice / jnp.take inside jit — fail loudly on the host side
        CHECK(tokens.shape[-1] <= self.param.max_len,
              f"sequence length {tokens.shape[-1]} exceeds max_len "
              f"{self.param.max_len}")
        for name, arr in (("token", tokens), ("label", labels)):
            CHECK(0 <= int(np.min(arr)) and int(np.max(arr)) < self.param.vocab_size,
                  f"{name} id out of vocab range [0, {self.param.vocab_size})")
        seq_ax = "seq" if self._has_seq else None
        sh = NamedSharding(self.mesh, P(self._batch_axes, seq_ax))
        t = jax.device_put(np.asarray(tokens, np.int32), sh)
        y = jax.device_put(np.asarray(labels, np.int32), sh)
        m = jax.device_put(np.asarray(mask, np.float32), sh)
        if self.param.grad_sync == "fused":
            self.params, self.opt_state, loss = self._step_fn(
                self.params, self.opt_state, t, y, m)
            return float(loss)
        _, stacked, loss = self._step_fn(self.params, self.opt_state, t, y, m)
        assert self._kv is not None
        keys = sorted(stacked)
        self._kv.push(keys, [stacked[k] for k in keys])
        pulled = self._kv.pull(keys)
        specs = self._param_specs()
        self.params = {
            k: jax.device_put(v, NamedSharding(self.mesh, specs[k]))
            for k, v in zip(keys, pulled)
        }
        return float(loss)

    def fit(self, tokens: np.ndarray, labels: np.ndarray, mask: np.ndarray,
            n_steps: int, warmup: int = 0) -> Tuple[float, float]:
        """Repeat steps on one batch (bench harness). Returns
        (final_loss, seconds for the timed steps)."""
        for _ in range(warmup):
            self.train_step(tokens, labels, mask)
        t0 = get_time()
        loss = float("nan")
        for _ in range(n_steps):
            loss = self.train_step(tokens, labels, mask)
        jax.block_until_ready(self.params["embed"])
        return loss, get_time() - t0

    def fit_chunked(self, tokens: np.ndarray, labels: np.ndarray,
                    mask: np.ndarray, n_steps: int, chunk: int = 10,
                    warmup_chunks: int = 1):
        """Bench harness: run ``n_steps`` fused
        optimizer steps as ``lax.scan`` chunks of ``chunk`` per dispatch
        (per-step host sync would dominate the measurement — see
        _build_step).  Returns ``(final_loss, seconds, chunk_times)``
        where chunk_times are in-order (steps_done, t) loss-fetch arrival
        timestamps — the same per-chunk audit evidence bench.py records
        for hist-GBT.  Timed region covers steady state only (warmup
        chunks compile + cache-warm first).  Requires grad_sync='fused'."""
        CHECK(self.params is not None, "call init_params() first")
        CHECK(self.param.grad_sync == "fused",
              "fit_chunked needs grad_sync='fused' (kvstore sync is a "
              "host round-trip per step by design)")
        seq_ax = "seq" if self._has_seq else None
        sh = NamedSharding(self.mesh, P(self._batch_axes, seq_ax))
        t = jax.device_put(np.asarray(tokens, np.int32), sh)
        y = jax.device_put(np.asarray(labels, np.int32), sh)
        m = jax.device_put(np.asarray(mask, np.float32), sh)
        CHECK(n_steps % chunk == 0,
              f"n_steps {n_steps} must be a multiple of chunk {chunk} "
              "(the scan program runs whole chunks; a silent overshoot "
              "would corrupt steps/s math in callers)")
        fn = self._make_multi(chunk)
        for _ in range(max(warmup_chunks, 1)):
            self.params, self.opt_state, losses = fn(
                self.params, self.opt_state, t, y, m)
        np.asarray(losses[-1:])       # real fetch = warmup completion
        t0 = get_time()
        loss_chunks = []
        done = 0
        while done < n_steps:
            self.params, self.opt_state, losses = fn(
                self.params, self.opt_state, t, y, m)
            loss_chunks.append(losses)
            done += chunk
        chunk_times = []
        fetched = 0
        final_loss = float("nan")
        for losses in loss_chunks:    # in-order arrival timestamps
            arr = np.asarray(losses)
            fetched += len(arr)
            chunk_times.append((fetched, get_time() - t0))
            final_loss = float(arr[-1])
        return final_loss, get_time() - t0, chunk_times
