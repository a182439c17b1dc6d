"""Pipeline parallelism (GPipe-style circular schedule) over a ``pipe``
mesh axis.

Beyond reference parity — upstream dmlc-core has no model math at all
(SURVEY.md §2e marks PP absent) — but the substrate reserves the ``pipe``
axis and a TPU-complete framework must populate it: at pod scale, layers
that don't fit one slice shard across stages and microbatches stream
through them over ICI.

The TPU-native formulation (no schedulers, no send/recv threads — the
reference world would build this with NCCL P2P + a runtime scheduler):

* every stage holds a CONTIGUOUS slab of layers as stacked ``[L_local,
  ...]`` arrays (a global ``[L, ...]`` array sharded over ``pipe``);
* one ``lax.scan`` runs ``n_micro + n_stages − 1`` ticks; each tick every
  stage applies its slab to its live microbatch and the activations
  ``ppermute`` one hop down the ring — the pipeline "schedule" is just a
  scan body the compiler overlaps;
* bubble ticks compute on zeros and are masked out of the loss, so
  ``jax.grad`` THROUGH the scan+ppermute yields exactly the pipelined
  backward (reverse ppermutes) with no hand-written schedule;
* ``jax.checkpoint`` on the stage function keeps the scan's saved state
  O(ticks · microbatch) instead of O(ticks · layers).

``pipeline_apply`` is the generic combinator (works inside any
``shard_map`` whose mesh has the axis); :class:`PipelineLM` is the
self-contained consumer — a masked-LM transformer on a (data, pipe) mesh
— used by tests and the multichip dryrun.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_core_tpu.base.logging import CHECK, CHECK_EQ
from dmlc_core_tpu.base.parameter import Parameter, field
from dmlc_core_tpu.base.timer import get_time
from dmlc_core_tpu.parallel.mesh import local_mesh

__all__ = ["pipeline_apply", "PipelineLM", "PipelineLMParam"]


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _replicated_loss_boundary(x: jax.Array, axis: str) -> jax.Array:
    """Identity forward; backward divides the cotangent by the axis size.

    After the ring-closing psum every shard redundantly computes the SAME
    downstream loss from the replicated pipeline output, so the psum's
    VJP sums S identical cotangents — S× the true gradient for everything
    upstream (all stage params, embeddings).  This boundary cancels the
    redundancy; downstream (head) grads are genuinely complete per shard
    and untouched."""
    return x


def _rlb_fwd(x, axis):
    return x, None


def _rlb_bwd(axis, _res, ct):
    return (ct / axis_size(axis),)


_replicated_loss_boundary.defvjp(_rlb_fwd, _rlb_bwd)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x_micro: jax.Array,          # [M, mb, ...] microbatched stage-0 input
    axis: str = "pipe",
) -> jax.Array:
    """Run ``x_micro`` through all pipeline stages; return [M, mb, ...]
    outputs as produced by the LAST stage (valid on every shard — the
    result is ppermuted back to close the ring, so callers can compute
    the loss on any stage).

    ``stage_fn(stage_params, x) -> y`` is THIS shard's slab of layers
    (already local under shard_map).  Ticks run ``M + S − 1`` times; at
    tick t, stage s works on microbatch ``t − s`` (zeros during bubble
    ticks).  Differentiable end-to-end: reverse-mode AD through the scan
    emits the reverse ppermutes of the backward pipeline.
    """
    S = axis_size(axis)
    idx = lax.axis_index(axis)
    M = x_micro.shape[0]
    mb_shape = x_micro.shape[1:]
    n_ticks = M + S - 1
    perm_fwd = [(i, (i + 1) % S) for i in range(S)]

    def tick(carry, t):
        buf, outs = carry                     # buf: [mb, ...] live input
        # stage 0 injects microbatch t (zeros when t ≥ M — bubble)
        inject = lax.dynamic_index_in_dim(
            x_micro, jnp.minimum(t, M - 1), 0, keepdims=False)
        inject = jnp.where(t < M, inject, jnp.zeros_like(inject))
        buf = jnp.where(idx == 0, inject, buf)
        y = jax.checkpoint(stage_fn)(stage_params, buf)
        # last stage completed microbatch t − (S−1): record it
        done_mb = t - (S - 1)
        outs = lax.cond(
            done_mb >= 0,
            lambda o: o.at[jnp.maximum(done_mb, 0)].set(
                jnp.where(idx == S - 1, y, o[jnp.maximum(done_mb, 0)])),
            lambda o: o,
            outs)
        # rotate activations one hop down the ring for the next tick
        buf_next = lax.ppermute(y, axis, perm_fwd)
        return (buf_next, outs), None

    buf0 = jnp.zeros(mb_shape, x_micro.dtype)
    outs0 = jnp.zeros((M, *mb_shape), x_micro.dtype)
    (_, outs), _ = lax.scan(tick, (buf0, outs0), jnp.arange(n_ticks))
    # only the last stage holds real outputs; close the ring so every
    # stage returns them (psum over a one-hot mask — cheap and exact);
    # the loss boundary cancels the S-fold cotangent of the redundant
    # per-shard downstream loss computation
    mine = jnp.where(idx == S - 1, outs, jnp.zeros_like(outs))
    return _replicated_loss_boundary(lax.psum(mine, axis), axis)


class PipelineLMParam(Parameter):
    """Small-transformer defaults sized for tests/dryruns; scale freely."""

    n_layers = field(int, default=4, lower_bound=1)
    d_model = field(int, default=64, lower_bound=8)
    n_heads = field(int, default=4, lower_bound=1)
    d_ff = field(int, default=128, lower_bound=8)
    vocab_size = field(int, default=256, lower_bound=16)
    max_len = field(int, default=64, lower_bound=8)
    n_micro = field(int, default=4, lower_bound=1,
                    description="microbatches per step (pipeline depth)")
    learning_rate = field(float, default=1e-2, lower_bound=0.0)


def _norm(x, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps)


class PipelineLM:
    """Masked-LM transformer on a (data, pipe) mesh.

    Layers live as ``[n_layers, ...]`` stacked arrays sharded over
    ``pipe`` (each stage scans its local slab); embedding/head are
    replicated and their grads psum over ``pipe`` (only the stage that
    touches them contributes non-zero cotangents).  The train step is
    one jitted shard_map program: DP grad sync (psum over ``data``) and
    the pipeline schedule compile into a single XLA module.
    """

    def __init__(self, param: Optional[PipelineLMParam] = None,
                 mesh: Optional[Mesh] = None, **kwargs: Any):
        self.param = param or PipelineLMParam()
        if kwargs:
            self.param.init(kwargs)
        self.mesh = mesh if mesh is not None else local_mesh()
        CHECK("data" in self.mesh.axis_names, "mesh needs a 'data' axis")
        self._has_pipe = "pipe" in self.mesh.axis_names
        self._pp = self.mesh.shape.get("pipe", 1)
        CHECK_EQ(self.param.n_layers % max(self._pp, 1), 0,
                 "n_layers % pipe != 0")
        self.params: Optional[Dict[str, jax.Array]] = None
        self._step_fn = None

    # -- parameters -----------------------------------------------------
    def _specs(self) -> Dict[str, P]:
        pipe = "pipe" if self._has_pipe else None
        return {
            "embed": P(), "pos": P(), "head": P(),
            # stacked per-layer arrays, layer dim sharded over pipe
            "wqkv": P(pipe), "wo": P(pipe),
            "w1": P(pipe), "b1": P(pipe), "w2": P(pipe), "b2": P(pipe),
        }

    def init_params(self, seed: int = 0) -> None:
        p = self.param
        rng = np.random.default_rng(seed)

        def g(*shape, scale=0.05):
            return (rng.normal(size=shape) * scale).astype(np.float32)

        L, D, F = p.n_layers, p.d_model, p.d_ff
        host = {
            "embed": g(p.vocab_size, D),
            "pos": g(p.max_len, D),
            "head": g(D, p.vocab_size),
            "wqkv": g(L, 3, D, D),
            "wo": g(L, D, D),
            "w1": g(L, D, F),
            "b1": np.zeros((L, F), np.float32),
            "w2": g(L, F, D),
            "b2": np.zeros((L, D), np.float32),
        }
        specs = self._specs()
        self.params = {k: jax.device_put(v, NamedSharding(self.mesh, specs[k]))
                       for k, v in host.items()}
        self._build_step()

    # -- stage computation ---------------------------------------------
    def _stage_fn(self, sp, x):
        """Apply this stage's slab of layers to activations [mb, s, D]."""
        p = self.param
        dh = p.d_model // p.n_heads

        def layer(x, lp):
            wqkv, wo, w1, b1, w2, b2 = lp
            h = _norm(x)
            qkv = jnp.einsum("bsd,cde->cbse", h, wqkv)
            q, k, v = [y.reshape(*y.shape[:2], p.n_heads, dh)
                       for y in (qkv[0], qkv[1], qkv[2])]
            scores = jnp.einsum("bshk,bthk->bhst", q, k) / np.sqrt(dh)
            attn = jnp.einsum("bhst,bthk->bshk", jax.nn.softmax(scores, -1), v)
            attn = attn.reshape(*attn.shape[:2], p.d_model)
            x = x + jnp.einsum("bse,ed->bsd", attn, wo)
            h = _norm(x)
            x = x + jnp.einsum("bsf,fd->bsd",
                               jax.nn.gelu(jnp.einsum("bsd,df->bsf", h, w1)
                                           + b1), w2) + b2
            return x, None

        x, _ = lax.scan(layer, x, (sp["wqkv"], sp["wo"], sp["w1"],
                                   sp["b1"], sp["w2"], sp["b2"]))
        return x

    def _build_step(self) -> None:
        p = self.param
        specs = self._specs()
        lr = p.learning_rate
        M = p.n_micro
        has_pipe = self._has_pipe

        def step(params, tokens, labels, mask):
            def loss_fn(ps):
                B, S = tokens.shape
                CHECK_EQ(B % M, 0, "local batch % n_micro != 0")
                mb = B // M
                x = (jnp.take(ps["embed"], tokens, axis=0)
                     + ps["pos"][None, :S])
                x_micro = x.reshape(M, mb, S, p.d_model)
                stage_params = {k: ps[k] for k in
                                ("wqkv", "wo", "w1", "b1", "w2", "b2")}
                if has_pipe:
                    y = pipeline_apply(self._stage_fn, stage_params,
                                       x_micro, axis="pipe")
                else:
                    y = jax.vmap(lambda xm: self._stage_fn(stage_params, xm)
                                 )(x_micro)
                y = _norm(y.reshape(B, S, p.d_model))
                logits = jnp.einsum("bsd,dv->bsv", y, ps["head"])
                logp = jax.nn.log_softmax(logits, -1)
                tok = jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
                mf = mask.astype(jnp.float32)
                return -(tok * mf).sum(), mf.sum()

            (ls, n), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            n_glob = lax.psum(n, "data")
            loss = lax.psum(ls, "data") / n_glob
            grads = jax.tree.map(lambda g: lax.psum(g, "data") / n_glob,
                                 grads)
            if has_pipe:
                # embed/pos flow through the stage-0 injection gate, so
                # only stage 0 holds non-zero cotangents — psum over pipe
                # completes them.  head/final-norm grads are ALREADY
                # complete on every stage (the pipeline output is psum-
                # replicated before the loss, so each stage differentiates
                # the full loss) and must NOT be psummed again.  Stacked
                # layer grads are pipe-sharded and local-complete.
                for k in ("embed", "pos"):
                    grads[k] = lax.psum(grads[k], "pipe")
            new_params = {k: params[k] - lr * grads[k] for k in params}
            return new_params, loss

        batch_spec = P("data")
        in_specs = ({k: specs[k] for k in specs},
                    batch_spec, batch_spec, batch_spec)
        mapped = shard_map(
            step, mesh=self.mesh, in_specs=in_specs,
            out_specs=({k: specs[k] for k in specs}, P()),
            check_vma=False)
        self._step_fn = jax.jit(mapped, donate_argnums=(0,))

        # scan-chunked multi-step program (fit_chunked): K steps per
        # dispatch, same rationale as BERT.fit_chunked — a per-step host
        # sync adds its latency to every sub-100ms step
        self._multi_cache: Dict[int, Any] = {}

        def make_multi(K: int):
            if K not in self._multi_cache:
                def multi(params, tokens, labels, mask):
                    def body(ps, _):
                        return step(ps, tokens, labels, mask)

                    return lax.scan(body, params, None, length=K)

                mapped_k = shard_map(
                    multi, mesh=self.mesh, in_specs=in_specs,
                    out_specs=({k: specs[k] for k in specs}, P()),
                    check_vma=False)
                self._multi_cache[K] = jax.jit(mapped_k, donate_argnums=(0,))
            return self._multi_cache[K]

        self._make_multi = make_multi

    # -- checkpointing (Stream/serializer consumer layer) ---------------
    _MODEL_MAGIC = b"DMLCTPU.PIPELM.v1\n"

    def save_model(self, uri: str) -> None:
        """Serialize hyperparams + params to any Stream URI (SURVEY.md
        §5 checkpoint layering; see models/checkpoint.py).  Pipe-sharded
        layer slabs gather to full arrays on save and re-shard on load,
        so the checkpoint is portable across pipe widths."""
        from dmlc_core_tpu.models.checkpoint import gather_tree, save_payload

        CHECK(self.params is not None, "save_model before init_params")
        save_payload(uri, self._MODEL_MAGIC, {
            "param": self.param.to_dict(),
            "params": gather_tree(self.params),
        })

    @classmethod
    def load_model(cls, uri: str,
                   mesh: Optional[Mesh] = None) -> "PipelineLM":
        from dmlc_core_tpu.models.checkpoint import load_payload

        payload = load_payload(uri, cls._MODEL_MAGIC)
        model = cls(mesh=mesh, **payload["param"])
        specs = model._specs()
        model.params = {
            k: jax.device_put(v, NamedSharding(model.mesh, specs[k]))
            for k, v in payload["params"].items()}
        model._build_step()
        return model

    # -- public API -----------------------------------------------------
    def train_step(self, tokens: np.ndarray, labels: np.ndarray,
                   mask: np.ndarray) -> float:
        CHECK(self.params is not None, "call init_params() first")
        sh = NamedSharding(self.mesh, P("data"))
        t = jax.device_put(np.asarray(tokens, np.int32), sh)
        y = jax.device_put(np.asarray(labels, np.int32), sh)
        m = jax.device_put(np.asarray(mask, np.float32), sh)
        self.params, loss = self._step_fn(self.params, t, y, m)
        return float(loss)

    def fit_chunked(self, tokens: np.ndarray, labels: np.ndarray,
                    mask: np.ndarray, n_steps: int, chunk: int = 10,
                    warmup_chunks: int = 1):
        """Run ``n_steps`` SGD steps as lax.scan chunks of ``chunk`` per
        dispatch; returns ``(final_loss, seconds, chunk_times)`` with
        in-order per-chunk loss-arrival timestamps (the bench audit
        pattern).  Steady-state timing: warmup chunks run first."""
        CHECK(self.params is not None, "call init_params() first")
        CHECK(n_steps % chunk == 0,
              f"n_steps {n_steps} must be a multiple of chunk {chunk}")
        sh = NamedSharding(self.mesh, P("data"))
        t = jax.device_put(np.asarray(tokens, np.int32), sh)
        y = jax.device_put(np.asarray(labels, np.int32), sh)
        m = jax.device_put(np.asarray(mask, np.float32), sh)
        fn = self._make_multi(chunk)
        for _ in range(max(warmup_chunks, 1)):
            self.params, losses = fn(self.params, t, y, m)
        np.asarray(losses[-1:])
        t0 = get_time()
        loss_chunks = []
        done = 0
        while done < n_steps:
            self.params, losses = fn(self.params, t, y, m)
            loss_chunks.append(losses)
            done += chunk
        chunk_times = []
        fetched = 0
        final_loss = float("nan")
        for losses in loss_chunks:
            arr = np.asarray(losses)
            fetched += len(arr)
            chunk_times.append((fetched, get_time() - t0))
            final_loss = float(arr[-1])
        return final_loss, get_time() - t0, chunk_times
