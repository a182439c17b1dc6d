"""GBLinear — the linear booster (XGBoost ``booster=gblinear``).

Reference-world context: XGBoost's second booster type; same objectives
and round structure as gbtree, but each boosting round updates the
weights of a regularized LINEAR model instead of growing a tree
(upstream ``gblinear.cc``'s shotgun/coordinate updaters).

TPU-first formulation: sequential coordinate descent serializes over
features — hostile to the MXU — so each round applies XGBoost's
*parallel (shotgun-style) damped coordinate update* to every feature at
once:

    delta_j = lr * ( -(Σ_i g_i·x_ij + λ·w_j) / (Σ_i h_i·x_ij² + λ) )

with an elastic-net soft-threshold for the L1 term (``alpha``).  One
round = grad/hess (elementwise) + the ``Xᵀg`` matvec + a fused
multiply-reduce for ``Σ h·x²`` (never materializing X² — a dot operand
would, doubling HBM residency) + one [F] ``psum`` across the data mesh —
the same in-step collective shape as the histogram sync, a few hundred
bytes per round.  Rounds run in lax.scan chunks per dispatch with the
same per-chunk arrival evidence as hist-GBT.

Objectives come from the shared OBJECTIVES registry (binary:logistic /
reg:squarederror).  Checkpoints go through the Stream layer
(models/checkpoint.py).
"""

from __future__ import annotations

from functools import lru_cache
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_core_tpu.base.logging import CHECK, CHECK_EQ
from dmlc_core_tpu.base.parameter import Parameter, field
from dmlc_core_tpu.base.timer import get_time
from dmlc_core_tpu.models.histgbt import OBJECTIVES
from dmlc_core_tpu.parallel.mesh import local_mesh

__all__ = ["GBLinear", "GBLinearParam"]

#: process-wide compiled K-round coordinate programs (see
#: histgbt._ROUND_FN_CACHE for the policy): keyed on
#: (mesh, K, objective, lr, lambda, alpha) — everything the trace bakes
#: in.  ``_ROUNDS_FN_CACHE.clear()`` releases the executables.
_ROUNDS_FN_CACHE: Dict[tuple, Any] = {}


@lru_cache(maxsize=256)
def _device_zeros_fn(mesh: Mesh, shape: tuple, dt):
    """Cached jitted sharded-zeros builder for fit_iter's device matrix
    (shape-keyed and bounded; a per-fit lambda recompiled every call).
    ``dt`` comes from ``_np_feature_dtype`` so buffer and slab dtypes
    share one mapping."""
    return jax.jit(
        lambda: jnp.zeros(shape, dt),
        out_shardings=NamedSharding(mesh, P("data", None)))


def _slab_write_impl(buf, slab, lo):
    """Donated dynamic-update-slice slab upload (module-level so its
    compiled programs persist across fits)."""
    return jax.lax.dynamic_update_slice(buf, slab, (lo, 0))


_slab_write = jax.jit(_slab_write_impl, donate_argnums=(0,))


class GBLinearParam(Parameter):
    """Hyperparameters (XGBoost gblinear names where they exist)."""

    n_rounds = field(int, default=100, lower_bound=1)
    learning_rate = field(float, default=0.5, lower_bound=0.0,
                          description="damping of the parallel "
                                      "coordinate step (eta)")
    reg_lambda = field(float, default=1.0, lower_bound=0.0,
                       description="L2 on weights")
    reg_alpha = field(float, default=0.0, lower_bound=0.0,
                      description="L1 on weights (soft-threshold)")
    scale_pos_weight = field(float, default=1.0, lower_bound=0.0,
                             description="binary:logistic — weight "
                                         "multiplier for positive rows "
                                         "(imbalanced data)")
    objective = field(str, default="binary:logistic",
                      enum=["binary:logistic", "reg:squarederror"])
    base_score = field(float, default=0.0)
    feature_dtype = field(str, default="float32",
                          enum=["float32", "bfloat16"],
                          description="device dtype of X: bfloat16 "
                                      "halves H2D bytes and HBM "
                                      "residency (7.8→3.9 GB at "
                                      "50M×39); the damped parallel "
                                      "coordinate step tolerates the "
                                      "~3-digit mantissa (oracle test "
                                      "vs f32 in tests/test_linear.py)")
    # no seed field: the parallel coordinate rounds are deterministic
    # (no subsampling) — an accepted-but-inert reproducibility knob
    # would mislead


class GBLinear:
    """Boosted linear model over a ``data``-axis mesh."""

    _MODEL_MAGIC = b"DMLCTPU.GBLIN.v1\n"

    def __init__(self, param: Optional[GBLinearParam] = None,
                 mesh: Optional[Mesh] = None, **kwargs: Any):
        self.param = param or GBLinearParam()
        if kwargs:
            self.param.init(kwargs)
        self.mesh = mesh if mesh is not None else local_mesh()
        CHECK("data" in self.mesh.axis_names, "mesh needs a 'data' axis")
        self._obj = OBJECTIVES[self.param.objective]
        self.weights: Optional[np.ndarray] = None    # [F]
        self.bias: float = 0.0
        self.last_fit_seconds: Optional[float] = None
        self.last_warmup_seconds: Optional[float] = None
        self.last_chunk_times: List[Tuple[int, float]] = []

    # -- training -------------------------------------------------------
    def _ndev(self) -> int:
        return int(np.prod([self.mesh.shape[a]
                            for a in self.mesh.axis_names]))

    def _build_rounds_fn(self, K: int):
        # process-wide program cache, same rationale as
        # histgbt._ROUND_FN_CACHE: jax.jit keys on function identity, so
        # per-instance closures recompile for every model (a GridSearchCV
        # over GBLinear pays seconds per candidate x fold otherwise).
        # Key = every config constant the trace bakes in; snapshot them
        # into locals so a cached program's retrace cannot read a later
        # live mutation of some instance's param
        p = self.param
        obj = self._obj
        lr = p.learning_rate
        lam = p.reg_lambda
        alpha = p.reg_alpha
        cache_key = (self.mesh, K, obj, lr, lam, alpha)
        cached = _ROUNDS_FN_CACHE.get(cache_key)
        if cached is not None:
            return cached

        def k_rounds(x_l, y_l, w_l, wvec, bias):
            def one_round(carry, _):
                wv, b = carry
                margin = x_l @ wv + b
                g, h = obj.grad_hess(margin, y_l)
                g = g * w_l
                h = h * w_l
                # [F] reductions: the only collectives in the round.
                # hsum as an elementwise-chain reduction (NOT h @ (x·x)):
                # a dot operand must materialize, and a full X² beside X
                # doubles HBM residency — 2×7.8 GB at 50M×39 overflows a
                # 16 GB chip; the fused multiply-reduce streams X once
                gsum = jax.lax.psum(g @ x_l, "data")         # Σ g·x_j
                hsum = jax.lax.psum(
                    (h[:, None] * x_l * x_l).sum(axis=0), "data")
                gb = jax.lax.psum(jnp.sum(g), "data")
                hb = jax.lax.psum(jnp.sum(h), "data")
                # per-coordinate quadratic model around wv:
                # min_d ½·denom·d² + grad_j·d + α(|wv+d| − |wv|)
                # closed form: w* = soft_threshold(denom·wv − grad_j, α)
                #                   / denom   (XGBoost CoordinateDelta)
                grad_j = gsum + lam * wv
                denom = hsum + lam
                # a dead coordinate (all-zero column, λ=0 → denom 0)
                # must stay put, not go NaN (XGBoost returns delta 0
                # when sum_hess vanishes)
                alive = denom > 1e-10
                safe = jnp.where(alive, denom, 1.0)
                raw = denom * wv - grad_j
                if alpha > 0.0:
                    target = (jnp.sign(raw)
                              * jnp.maximum(jnp.abs(raw) - alpha, 0.0)
                              / safe)
                else:
                    target = raw / safe       # == wv − grad_j/denom
                target = jnp.where(alive, target, wv)
                wv2 = wv + lr * (target - wv)
                b2 = b - lr * gb / (hb + 1e-6)
                return (wv2, b2), None

            (wv, b), _ = jax.lax.scan(one_round, (wvec, bias), None,
                                      length=K)
            return wv, b

        mapped = shard_map(
            k_rounds, mesh=self.mesh,
            in_specs=(P("data", None), P("data"), P("data"), P(), P()),
            out_specs=(P(), P()),
            check_vma=False)
        fn = jax.jit(mapped)
        _ROUNDS_FN_CACHE[cache_key] = fn
        return fn

    def _np_feature_dtype(self):
        """numpy-compatible dtype of the device feature matrix
        (ml_dtypes bfloat16 via jnp when requested)."""
        return (jnp.bfloat16 if self.param.feature_dtype == "bfloat16"
                else np.float32)

    def _fold_scale_pos_weight(self, y, weight):
        """Shared XGBoost scale_pos_weight fold (histgbt's is THE one
        implementation); called from fit AND fit_iter."""
        from dmlc_core_tpu.models.histgbt import fold_scale_pos_weight

        return fold_scale_pos_weight(self.param, y, weight)

    def fit(self, X: np.ndarray, y: np.ndarray,
            weight: Optional[np.ndarray] = None,
            warmup_rounds: int = 0) -> "GBLinear":
        p = self.param
        X = np.ascontiguousarray(X, np.float32)
        y = np.ascontiguousarray(y, np.float32)
        n, F = X.shape
        CHECK_EQ(len(y), n, "X/y row mismatch")
        weight = self._fold_scale_pos_weight(y, weight)
        ndev = self._ndev()
        pad = (-n) % ndev
        mask = np.ones(n + pad, np.float32)
        if weight is not None:
            mask[:n] = weight
        if pad:
            X = np.concatenate([X, np.zeros((pad, F), np.float32)])
            y = np.concatenate([y, np.zeros(pad, np.float32)])
            mask[n:] = 0.0
        dt = self._np_feature_dtype()
        if dt is not np.float32:
            X = X.astype(dt)              # halves the H2D bytes
        sh_m = NamedSharding(self.mesh, P("data", None))
        sh_r = NamedSharding(self.mesh, P("data"))
        x_d = jax.device_put(X, sh_m)
        y_d = jax.device_put(y, sh_r)
        w_d = jax.device_put(mask, sh_r)
        return self._fit_device(x_d, y_d, w_d, F, warmup_rounds)

    def _fit_device(self, x_d, y_d, w_d, F: int,
                    warmup_rounds: int) -> "GBLinear":
        """Shared training body over device-resident (X, y, mask) —
        :meth:`fit` uploads in one put, :meth:`fit_iter` streams pages
        into the buffer first."""
        p = self.param
        K = min(p.n_rounds, 25)
        kfn = self._build_rounds_fn(K)
        rem = p.n_rounds % K
        rem_fn = self._build_rounds_fn(rem) if rem else None

        wvec = jnp.zeros(F, jnp.float32)
        bias = jnp.asarray(p.base_score, jnp.float32)
        t_w = get_time()
        if warmup_rounds > 0:
            # warm BOTH programs (the remainder chunk would otherwise
            # compile inside the timed region — same rule as HistGBT)
            warm = kfn(x_d, y_d, w_d, wvec, bias)
            np.asarray(warm[0][:1])
            if rem_fn is not None:
                warm = rem_fn(x_d, y_d, w_d, wvec, bias)
                np.asarray(warm[0][:1])
        self.last_warmup_seconds = get_time() - t_w

        t0 = get_time()
        self.last_chunk_times = []
        done = 0
        while done < p.n_rounds:
            fn = kfn if p.n_rounds - done >= K else rem_fn
            wvec, bias = fn(x_d, y_d, w_d, wvec, bias)
            done += K if fn is kfn else rem
            np.asarray(wvec[:1])      # chunk boundary evidence
            self.last_chunk_times.append((done, get_time() - t0))
        self.weights = np.asarray(wvec)
        self.bias = float(np.asarray(bias))
        self.last_fit_seconds = get_time() - t0
        return self

    def fit_iter(self, row_iter, num_col: Optional[int] = None,
                 warmup_rounds: int = 0,
                 rows_per_upload: int = 2_000_000) -> "GBLinear":
        """Train over a :class:`RowBlockIter` (LibSVM/LibFM pages — the
        large-sparse-data niche gblinear exists for).

        Pages stream through a ``rows_per_upload``-row staging buffer
        straight into the device-resident feature matrix (donated
        ``dynamic_update_slice`` writes), so HOST memory stays bounded
        by one slab — the full dense matrix never exists on the host
        (the r3 path materialized all 7.8 GB at 50M×39 and then paid a
        second full copy inside fit's padding).  The coordinate rounds
        then run device-resident exactly like :meth:`fit` (each round
        needs the full ``Xᵀg`` reduction, so a per-round page loop
        would pay O(pages) dispatches per round — the per-dispatch
        latency trap the hist-GBT page loop documents).  There is no uint8 binning to
        shrink a linear model's features, but
        ``feature_dtype="bfloat16"`` halves both transfer and HBM
        (3.9 GB at 50M×39), with an f32-oracle test guarding the
        damped-coordinate tolerance."""
        p = self.param
        F = max(num_col or 0, row_iter.num_col)
        CHECK(F > 0, "fit_iter: no columns (num_col unset and the "
                     "iterator reports width 0)")
        # row count from iterator metadata when available (BasicRowIter
        # and DiskRowIter track it), else one counting pass
        n = row_iter.num_rows
        counted = False
        if n is None:
            # NOTE this counting pass iterates row_iter a first time, so
            # the fill pass below relies on the RowBlockIter rewind
            # contract (BeforeFirst semantics: iterating again restarts
            # from the first block).  All in-repo iterators honor it; a
            # one-shot generator wrapped as an iterator does not.
            counted = True
            n = sum(b.size for b in row_iter)
        CHECK(n > 0, "fit_iter: iterator yielded no rows")
        ndev = self._ndev()
        pad = (-n) % ndev
        n_tot = n + pad
        dt = self._np_feature_dtype()
        sh_r = NamedSharding(self.mesh, P("data"))
        # device-side zeros: pad rows are already correct, and partial
        # final slabs only need their REAL rows written
        x_d = _device_zeros_fn(self.mesh, (n_tot, F), dt)()
        write = _slab_write
        from dmlc_core_tpu.data.iter import iter_dense_slabs

        R = max(1, min(rows_per_upload, n_tot))
        y = np.zeros(n_tot, np.float32)
        w = np.zeros(n_tot, np.float32)
        lo = 0              # device row offset / total rows consumed
        for xs, ys, ws in iter_dense_slabs(row_iter, F, R):
            rows = len(ys)
            # astype/copy ALWAYS materializes a fresh slab: device_put
            # may alias the host buffer zero-copy (CPU backend), and the
            # generator refills its staging buffer on the next yield
            slab = (xs.astype(dt) if dt is not np.float32 else xs.copy())
            x_d = write(x_d, jnp.asarray(slab), lo)
            y[lo:lo + rows] = ys
            w[lo:lo + rows] = self._fold_scale_pos_weight(ys, ws)
            lo += rows
        CHECK(not (counted and lo == 0),
              "fit_iter: iterator yielded rows in the counting pass but "
              "none in the fill pass — it is not re-iterable (RowBlockIter "
              "contract: iteration must rewind); pass num_col/num_rows or "
              "use a rewindable iterator")
        CHECK_EQ(lo, n, "fit_iter: iterator row count inconsistent")
        w[n:] = 0.0                     # pad rows weigh 0
        y_d = jax.device_put(y, sh_r)
        w_d = jax.device_put(w, sh_r)
        return self._fit_device(x_d, y_d, w_d, F, warmup_rounds)

    def fit_ps(self, row_iter, kv, num_col: Optional[int] = None,
               batch_rows: int = 8192, n_epochs: int = 1,
               name: str = "gblinear", finalize: bool = True
               ) -> "GBLinear":
        """Web-scale sparse SGD over a parameter server.

        The complement of :meth:`fit_iter` for feature spaces that do
        NOT fit a dense device matrix (10M+-cardinality CTR hashing
        spaces): weights live range-sharded on the PS fleet behind
        ``kv`` (a dist_async :class:`~..parallel.kvstore.KVStore`);
        each CSR minibatch pulls only the feature ids it touches,
        computes the (mean-loss) gradient on the host straight off the
        ``offset``/``index``/``value`` arrays, and pushes it back
        asynchronously — the server applies SGD with the store's
        learning_rate on arrival.  One :meth:`tick` per minibatch is
        the SSP round; staleness across workers is bounded by
        ``DMLC_PS_STALENESS``.

        ``reg_lambda`` is applied lazily (touched coordinates only),
        scaled 1/n alongside the data term — the sum-loss
        ``Σ lᵢ + λ/2‖w‖²`` divided by batch size, the standard sparse
        compromise (untouched features decay only when next seen).

        ``finalize`` pulls the full dense weight vector into
        ``self.weights`` / ``self.bias`` at the end so
        :meth:`predict` works; pass False at true 10M+ scale and
        serve from the fleet instead.
        """
        p = self.param
        F = max(num_col or 0, getattr(row_iter, "num_col", 0) or 0)
        CHECK(F > 0, "fit_ps: no columns (num_col unset and the "
                     "iterator reports width 0)")
        from dmlc_core_tpu.data.iter import iter_csr_minibatches

        # bias rides at id F: one PS array, one pull per minibatch
        kv.init_sparse(name, n_keys=F + 1)
        logistic = p.objective == "binary:logistic"
        lam = p.reg_lambda
        t0 = get_time()
        for _ in range(int(n_epochs)):
            for blk in iter_csr_minibatches(row_iter, batch_rows):
                n = blk.size
                vals = (blk.value if blk.value is not None
                        else np.ones(blk.nnz, np.float32))
                uids, inv = np.unique(blk.index, return_inverse=True)
                ids = np.concatenate([uids, [F]])
                w = np.asarray(kv.pull_sparse(name, ids), np.float32)
                rows = np.repeat(np.arange(n),
                                 np.diff(blk.offset)).astype(np.int64)
                margin = np.full(n, w[-1] + p.base_score, np.float32)
                np.add.at(margin, rows, w[:-1][inv] * vals)
                y = blk.label
                if logistic:
                    g = 1.0 / (1.0 + np.exp(-margin)) - y
                else:
                    g = margin - y
                sw = self._fold_scale_pos_weight(y, blk.weight)
                if sw is not None:
                    g = g * sw
                gfeat = np.zeros(len(uids), np.float32)
                np.add.at(gfeat, inv, g[rows] * vals)
                grad = np.concatenate([gfeat + lam * w[:-1],
                                       [g.sum()]]) / n
                kv.push_sparse(name, ids, grad.astype(np.float32))
                kv.tick()
        kv.flush()
        self.last_fit_seconds = get_time() - t0
        if finalize:
            ids = np.arange(F + 1, dtype=np.int64)
            w = np.asarray(kv.pull_sparse(name, ids), np.float32)
            self.weights = w[:-1]
            self.bias = float(w[-1]) + p.base_score
        return self

    # -- inference ------------------------------------------------------
    def predict(self, X: np.ndarray,
                output_margin: bool = False) -> np.ndarray:
        CHECK(self.weights is not None, "predict before fit")
        X = np.ascontiguousarray(X, np.float32)
        margin = X @ self.weights + self.bias
        if output_margin or self.param.objective != "binary:logistic":
            return margin.astype(np.float32)
        return np.asarray(jax.nn.sigmoid(jnp.asarray(margin)))

    def predict_iter(self, row_iter, output_margin: bool = False,
                     batch_rows: int = 2_000_000) -> np.ndarray:
        """Streaming prediction over a :class:`RowBlockIter` — score the
        pages :meth:`fit_iter` trained on without ever holding the
        dense matrix (one ``batch_rows`` staging slab bounds host
        memory; each slab is a single numpy matvec)."""
        from dmlc_core_tpu.data.iter import iter_dense_slabs

        CHECK(self.weights is not None, "predict before fit")
        F = len(self.weights)
        outs = [self.predict(xb, output_margin=output_margin)
                for xb, _, _ in iter_dense_slabs(row_iter, F, batch_rows)]
        if not outs:
            return np.zeros(0, np.float32)
        return np.concatenate(outs) if len(outs) > 1 else outs[0]

    # -- checkpointing --------------------------------------------------
    def save_model(self, uri: str) -> None:
        """Serialize hyperparams + weights to any Stream URI."""
        from dmlc_core_tpu.models.checkpoint import save_payload

        CHECK(self.weights is not None, "save_model before fit")
        save_payload(uri, self._MODEL_MAGIC, {
            "param": self.param.to_dict(),
            "weights": self.weights,
            "bias": self.bias,
        })

    @classmethod
    def load_model(cls, uri: str, mesh: Optional[Mesh] = None) -> "GBLinear":
        from dmlc_core_tpu.models.checkpoint import load_payload

        payload = load_payload(uri, cls._MODEL_MAGIC)
        model = cls(mesh=mesh, **payload["param"])
        model.weights = np.asarray(payload["weights"], np.float32)
        model.bias = float(payload["bias"])
        return model
