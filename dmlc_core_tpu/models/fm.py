"""Factorization machines, TPU-native — the LibFM-format consumer.

The reference ships a LibFM parser (``src/data/libfm_parser.h`` — SURVEY
§2b) whose natural consumer is a factorization machine; this closes that
loop the way hist-GBT closes the LibSVM one.  Second-order FM (Rendle
2010):

    ŷ(x) = w₀ + Σᵢ wᵢxᵢ + ½ Σ_k [(Σᵢ v_{ik} xᵢ)² − Σᵢ v_{ik}² xᵢ²]

computed with the O(n·k) "sum-of-squares" identity — two dense [B, F] ×
[F, K] matmuls per batch, exactly the MXU's shape.  Rows are sharded
over the mesh's ``data`` axis and gradients psum in-step (the same
rabit-allreduce replacement as hist-GBT); the optimizer is Adam with f32
state.  Sparse CSR pages from any :class:`RowBlockIter` densify
per-batch (the hist-GBT external-memory convention — missing = 0).

Objectives: ``binary:logistic`` or ``reg:squarederror`` (shared with the
GBT registry's semantics).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_core_tpu.base.logging import CHECK, CHECK_EQ
from dmlc_core_tpu.base.parameter import Parameter, field
from dmlc_core_tpu.base.timer import get_time
from dmlc_core_tpu.parallel.mesh import local_mesh

__all__ = ["FM", "FMParam"]

#: process-wide compiled Adam-step programs (see
#: histgbt._ROUND_FN_CACHE for the policy): keyed on every config
#: constant the trace bakes in.
_STEP_FN_CACHE: Dict[tuple, Any] = {}


class FMParam(Parameter):
    """Hyperparameters (libFM-compatible names where they exist)."""

    n_factors = field(int, default=8, lower_bound=1, description="k")
    learning_rate = field(float, default=0.05, lower_bound=0.0)
    reg_w = field(float, default=1e-4, lower_bound=0.0,
                  description="L2 on linear weights")
    reg_v = field(float, default=1e-4, lower_bound=0.0,
                  description="L2 on factor matrix")
    n_epochs = field(int, default=10, lower_bound=1)
    batch_size = field(int, default=8192, lower_bound=16)
    objective = field(str, default="binary:logistic",
                      enum=["binary:logistic", "reg:squarederror"])
    init_scale = field(float, default=0.01, lower_bound=0.0)
    seed = field(int, default=0)


@jax.jit
def _fm_margin(params, x):
    """ŷ raw margin for dense x [B, F] — O(B·F·k) via the FM identity."""
    lin = x @ params["w"] + params["w0"]                    # [B]
    xv = x @ params["v"]                                    # [B, K]
    x2v2 = (x * x) @ (params["v"] * params["v"])            # [B, K]
    return lin + 0.5 * jnp.sum(xv * xv - x2v2, axis=1)


class FM:
    """Train/predict API over a ``data``-axis mesh.

    ``fit(X, y)`` for in-core dense/CSR-densified arrays;
    ``fit_iter(row_iter)`` streams :class:`RowBlockIter` pages (the
    LibFM/LibSVM file path) without materializing the dataset.
    """

    def __init__(self, param: Optional[FMParam] = None,
                 mesh: Optional[Mesh] = None, **kwargs: Any):
        self.param = param or FMParam()
        if kwargs:
            self.param.init(kwargs)
        self.mesh = mesh if mesh is not None else local_mesh()
        CHECK("data" in self.mesh.axis_names, "mesh needs a 'data' axis")
        self.params: Optional[Dict[str, jax.Array]] = None
        self._opt: Optional[Dict[str, Any]] = None
        self._step_fn = None
        self._n_features: Optional[int] = None
        self.last_fit_seconds: Optional[float] = None

    # -- setup ----------------------------------------------------------
    def _init_state(self, n_features: int) -> None:
        p = self.param
        rng = np.random.default_rng(p.seed)
        self._n_features = n_features
        host = {
            "w0": np.zeros((), np.float32),
            "w": np.zeros(n_features, np.float32),
            "v": (rng.normal(size=(n_features, p.n_factors))
                  * p.init_scale).astype(np.float32),
        }
        rep = NamedSharding(self.mesh, P())
        self.params = {k: jax.device_put(v, rep) for k, v in host.items()}
        self._opt = {
            "m": jax.tree.map(jnp.zeros_like, self.params),
            "s": jax.tree.map(jnp.zeros_like, self.params),
            "t": jnp.zeros((), jnp.int32),
        }
        self._build_step()

    def _build_step(self) -> None:
        p = self.param
        logistic = p.objective == "binary:logistic"
        lr, b1, b2, eps = p.learning_rate, 0.9, 0.999, 1e-8
        # snapshot the remaining traced constants (reg terms) and share
        # the compiled step across same-config instances
        reg_w, reg_v = p.reg_w, p.reg_v
        cache_key = (self.mesh, logistic, lr, reg_w, reg_v)
        cached = _STEP_FN_CACHE.get(cache_key)
        if cached is not None:
            self._step_fn = cached
            return

        def step(params, opt, x_l, y_l, w_l):
            def local_sum(ps):
                # LOCAL weighted loss sum only — differentiating through
                # an in-loss psum would scale the data gradient by the
                # shard count (psum's transpose is psum) while leaving
                # the reg term 1×; grads psum explicitly below instead
                margin = _fm_margin(ps, x_l)
                if logistic:
                    per_row = (jax.nn.softplus(margin)
                               - y_l * margin)            # logloss on margin
                else:
                    per_row = 0.5 * (margin - y_l) ** 2
                return jnp.sum(per_row * w_l)

            loss_sum, grads = jax.value_and_grad(local_sum)(params)
            n_glob = lax.psum(jnp.sum(w_l), "data")
            grads = jax.tree.map(
                lambda g: lax.psum(g, "data") / n_glob, grads)
            # analytic L2 grads (the reg term is replicated, not sharded)
            grads["w"] = grads["w"] + 2 * reg_w * params["w"]
            grads["v"] = grads["v"] + 2 * reg_v * params["v"]
            loss = (lax.psum(loss_sum, "data") / n_glob
                    + reg_w * jnp.sum(params["w"] ** 2)
                    + reg_v * jnp.sum(params["v"] ** 2))
            t = opt["t"] + 1
            tf = t.astype(jnp.float32)

            def adam(mp, sp, g, w):
                m = b1 * mp + (1 - b1) * g
                s = b2 * sp + (1 - b2) * g * g
                mhat = m / (1 - b1 ** tf)
                shat = s / (1 - b2 ** tf)
                return m, s, w - lr * mhat / (jnp.sqrt(shat) + eps)

            new_m, new_s, new_p = {}, {}, {}
            for key in params:
                new_m[key], new_s[key], new_p[key] = adam(
                    opt["m"][key], opt["s"][key], grads[key], params[key])
            return new_p, {"m": new_m, "s": new_s, "t": t}, loss

        self._step_fn = jax.jit(shard_map(
            step, mesh=self.mesh,
            in_specs=(P(), {"m": P(), "s": P(), "t": P()},
                      P("data", None), P("data"), P("data")),
            out_specs=(P(), {"m": P(), "s": P(), "t": P()}, P()),
            check_vma=False), donate_argnums=(0, 1))
        _STEP_FN_CACHE[cache_key] = self._step_fn

    # -- training -------------------------------------------------------
    def _ndev(self) -> int:
        return int(np.prod([self.mesh.shape[a]
                            for a in self.mesh.axis_names]))

    def _run_batch(self, xb, yb, wb):
        # pad EVERY batch to the fixed (batch_size-rounded) shape so the
        # jitted step compiles once — variable trailing-batch shapes
        # would otherwise trigger a fresh XLA compile per distinct size
        ndev = self._ndev()
        target = self.param.batch_size + (-self.param.batch_size) % ndev
        pad = max(target, ndev) - len(yb)
        if pad:
            xb = np.concatenate([xb, np.zeros((pad, xb.shape[1]),
                                              np.float32)])
            yb = np.concatenate([yb, np.zeros(pad, np.float32)])
            wb = np.concatenate([wb, np.zeros(pad, np.float32)])
        sh_m = NamedSharding(self.mesh, P("data", None))
        sh_r = NamedSharding(self.mesh, P("data"))
        self.params, self._opt, loss = self._step_fn(
            self.params, self._opt,
            jax.device_put(xb, sh_m), jax.device_put(yb, sh_r),
            jax.device_put(wb, sh_r))
        return float(loss)

    def fit(self, X: np.ndarray, y: np.ndarray,
            weight: Optional[np.ndarray] = None) -> "FM":
        p = self.param
        X = np.ascontiguousarray(X, np.float32)
        y = np.ascontiguousarray(y, np.float32)
        CHECK_EQ(len(X), len(y), "X/y row mismatch")
        if self.params is None:
            self._init_state(X.shape[1])
        else:
            CHECK_EQ(X.shape[1], self._n_features, "feature-count mismatch")
        w = (np.ones(len(y), np.float32) if weight is None
             else np.asarray(weight, np.float32))
        rng = np.random.default_rng(p.seed)
        t0 = get_time()
        for _epoch in range(p.n_epochs):
            order = rng.permutation(len(y))
            for lo in range(0, len(y), p.batch_size):
                sel = order[lo:lo + p.batch_size]
                self.last_loss = self._run_batch(X[sel], y[sel], w[sel])
        jax.block_until_ready(self.params["w"])
        self.last_fit_seconds = get_time() - t0
        return self

    def fit_iter(self, row_iter, num_col: Optional[int] = None) -> "FM":
        """Stream RowBlockIter pages (LibFM/LibSVM files) — one epoch per
        pass over the iterator, ``n_epochs`` passes."""
        p = self.param
        F = max(num_col or 0, row_iter.num_col)
        CHECK(F > 0, "fit_iter: empty input")
        if self.params is None:
            self._init_state(F)
        t0 = get_time()
        for _epoch in range(p.n_epochs):
            for block in row_iter:
                X = block.to_dense(F)
                y = np.asarray(block.label, np.float32)
                w = (np.asarray(block.weight, np.float32)
                     if block.weight is not None
                     else np.ones(len(y), np.float32))
                for lo in range(0, len(y), p.batch_size):
                    self.last_loss = self._run_batch(
                        X[lo:lo + p.batch_size], y[lo:lo + p.batch_size],
                        w[lo:lo + p.batch_size])
        jax.block_until_ready(self.params["w"])
        self.last_fit_seconds = get_time() - t0
        return self

    def fit_ps(self, row_iter, kv, num_col: Optional[int] = None,
               batch_rows: int = 8192, name: str = "fm",
               finalize: bool = True) -> "FM":
        """Web-scale sparse FM-SGD over a parameter server.

        Two PS arrays carry the model: ``{name}:w`` [F+1] (linear
        weights, bias at id F, zero-init) and ``{name}:v`` [F, k]
        (factor matrix, server-side Normal(0, init_scale) init seeded
        by key range — zeros would be a stuck point of the v-gradient).
        Each CSR minibatch pulls only the rows its feature ids touch,
        computes the exact FM gradient on the host via the O(nnz·k)
        identity, and pushes back asynchronously (server-side SGD, not
        Adam — per-coordinate optimizer state on 10M+ rows belongs to
        the fleet, not the wire).  One :meth:`tick` per minibatch;
        ``n_epochs`` passes over the iterator.

        ``reg_w`` / ``reg_v`` apply lazily (touched rows only) like
        :meth:`GBLinear.fit_ps`'s reg_lambda.  ``finalize`` pulls both
        arrays dense into ``self.params`` so :meth:`predict` works —
        skip it at true 10M+ scale.
        """
        p = self.param
        F = max(num_col or 0, getattr(row_iter, "num_col", 0) or 0)
        CHECK(F > 0, "fit_ps: no columns (num_col unset and the "
                     "iterator reports width 0)")
        from dmlc_core_tpu.data.iter import iter_csr_minibatches

        K = p.n_factors
        wname, vname = f"{name}:w", f"{name}:v"
        kv.init_sparse(wname, n_keys=F + 1)
        kv.init_sparse(vname, n_keys=F, width=(K,),
                       init_scale=p.init_scale, seed=p.seed)
        logistic = p.objective == "binary:logistic"
        t0 = get_time()
        for _epoch in range(p.n_epochs):
            for blk in iter_csr_minibatches(row_iter, batch_rows):
                n = blk.size
                vals = (np.asarray(blk.value, np.float32)
                        if blk.value is not None
                        else np.ones(blk.nnz, np.float32))
                uids, inv = np.unique(blk.index, return_inverse=True)
                wids = np.concatenate([uids, [F]])
                w = np.asarray(kv.pull_sparse(wname, wids), np.float32)
                V = np.asarray(kv.pull_sparse(vname, uids), np.float32)
                rows = np.repeat(np.arange(n),
                                 np.diff(blk.offset)).astype(np.int64)
                vnz = V[inv]                                  # [nnz, K]
                xnz = vals[:, None]
                lin = np.full(n, w[-1], np.float32)
                np.add.at(lin, rows, w[:-1][inv] * vals)
                xv = np.zeros((n, K), np.float32)             # Σ v·x
                np.add.at(xv, rows, vnz * xnz)
                x2v2 = np.zeros((n, K), np.float32)           # Σ v²x²
                np.add.at(x2v2, rows, vnz * vnz * xnz * xnz)
                margin = lin + 0.5 * np.sum(xv * xv - x2v2, axis=1)
                y = np.asarray(blk.label, np.float32)
                if logistic:
                    g = 1.0 / (1.0 + np.exp(-margin)) - y
                else:
                    g = margin - y
                if blk.weight is not None:
                    g = g * blk.weight
                gr = g[rows]                                  # [nnz]
                gw = np.zeros(len(uids), np.float32)
                np.add.at(gw, inv, gr * vals)
                gv = np.zeros((len(uids), K), np.float32)
                np.add.at(gv, inv,
                          gr[:, None] * (xnz * xv[rows] - vnz * xnz * xnz))
                kv.push_sparse(wname, wids, np.concatenate(
                    [gw + 2 * p.reg_w * w[:-1], [g.sum()]]) / n)
                kv.push_sparse(vname, uids,
                               (gv + 2 * p.reg_v * V) / n)
                kv.tick()
        kv.flush()
        self.last_fit_seconds = get_time() - t0
        if finalize:
            wfull = np.asarray(
                kv.pull_sparse(wname, np.arange(F + 1, dtype=np.int64)),
                np.float32)
            vfull = np.asarray(
                kv.pull_sparse(vname, np.arange(F, dtype=np.int64)),
                np.float32)
            if self.params is None:
                self._init_state(F)
            rep = NamedSharding(self.mesh, P())
            self.params = {
                "w0": jax.device_put(np.float32(wfull[-1]), rep),
                "w": jax.device_put(wfull[:-1], rep),
                "v": jax.device_put(vfull, rep),
            }
        return self

    # -- checkpointing (Stream/serializer consumer layer) ---------------
    _MODEL_MAGIC = b"DMLCTPU.FM.v1\n"

    def save_model(self, uri: str) -> None:
        """Serialize hyperparams + weights + Adam state to any Stream
        URI (SURVEY.md §5 checkpoint layering)."""
        from dmlc_core_tpu.models.checkpoint import gather_tree, save_payload

        CHECK(self.params is not None, "save_model before fit")
        save_payload(uri, self._MODEL_MAGIC, {
            "param": self.param.to_dict(),
            "n_features": self._n_features,
            "params": gather_tree(self.params),
            "opt_m": gather_tree(self._opt["m"]),
            "opt_s": gather_tree(self._opt["s"]),
            "opt_t": int(np.asarray(self._opt["t"])),
        })

    @classmethod
    def load_model(cls, uri: str, mesh: Optional[Mesh] = None) -> "FM":
        """Inverse of :meth:`save_model`; predicts immediately and
        resumes training exactly (Adam moments + step restored)."""
        from dmlc_core_tpu.models.checkpoint import load_payload

        payload = load_payload(uri, cls._MODEL_MAGIC)
        model = cls(mesh=mesh, **payload["param"])
        model._init_state(payload["n_features"])
        rep = NamedSharding(model.mesh, P())
        model.params = {k: jax.device_put(v, rep)
                        for k, v in payload["params"].items()}
        model._opt = {
            "m": {k: jax.device_put(v, rep)
                  for k, v in payload["opt_m"].items()},
            "s": {k: jax.device_put(v, rep)
                  for k, v in payload["opt_s"].items()},
            "t": jnp.asarray(payload["opt_t"], jnp.int32),
        }
        return model

    # -- inference ------------------------------------------------------
    def predict(self, X: np.ndarray, output_margin: bool = False
                ) -> np.ndarray:
        CHECK(self.params is not None, "predict before fit")
        X = np.ascontiguousarray(X, np.float32)
        CHECK_EQ(X.shape[1], self._n_features, "feature-count mismatch")
        margin = _fm_margin(self.params, jnp.asarray(X))
        if output_margin or self.param.objective != "binary:logistic":
            return np.asarray(margin)
        return np.asarray(jax.nn.sigmoid(margin))
