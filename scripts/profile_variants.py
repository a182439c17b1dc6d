"""Bisect round_body cost: time jitted round variants with components
knocked out (chained iterations, one real fetch at the end)."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from dmlc_core_tpu.models.histgbt import _make_best_split
from dmlc_core_tpu.ops.histogram import build_histogram
from dmlc_core_tpu.ops.quantile import apply_bins, compute_cuts
from dmlc_core_tpu.parallel.mesh import local_mesh

ROWS, F, B, DEPTH = 4_000_000, 28, 256, 6
ITERS = int(os.environ.get("ITERS", 8))

rng = np.random.default_rng(0)
X = rng.normal(size=(ROWS, F)).astype(np.float32)
y = (rng.random(ROWS) > 0.5).astype(np.float32)
mesh = local_mesh()
row_sh = NamedSharding(mesh, P("data"))
mat_sh = NamedSharding(mesh, P("data", None))
bins = apply_bins(jax.device_put(X, mat_sh), compute_cuts(X, B))
y_d = jax.device_put(y, row_sh)
w_d = jax.device_put(np.ones(ROWS, np.float32), row_sh)
preds0 = jax.device_put(np.zeros(ROWS, np.float32), row_sh)

best_split = _make_best_split(B, 1.0, 0.0, 1.0)
best_split_leaf = _make_best_split(B, 1.0, 0.0, 1.0, with_child_sums=True)


def table_select(table, node, n_entries):
    n_iota = jnp.arange(n_entries, dtype=jnp.int32)[None, :]
    oh = node[:, None] == n_iota
    return jnp.sum(jnp.where(oh, table[None, :], 0), axis=1)


def make_round(with_hist=True, with_split=True, with_descend=True,
               with_leaf=True):
    def round_body(bins_l, y_l, w_l, preds_l):
        p = jax.nn.sigmoid(preds_l)
        g = (p - y_l) * w_l
        h = p * (1 - p) * w_l
        node = jnp.zeros(bins_l.shape[0], jnp.int32)
        gsum = jnp.zeros(64, jnp.float32)
        hsum = jnp.ones(64, jnp.float32)
        for level in range(DEPTH):
            n_nodes = 1 << level
            if with_hist:
                hist = build_histogram(bins_l, node, g, h, n_nodes, B, "pallas")
                hist = jax.lax.psum(hist, "data")
            else:
                hist = jnp.zeros((2, n_nodes, F, B), jnp.float32) + g[0]
            if with_split:
                if level == DEPTH - 1:
                    feat, thr, _gn, gsum, hsum = best_split_leaf(hist)
                else:
                    feat, thr, _gn = best_split(hist)
            else:
                feat = jnp.zeros(n_nodes, jnp.int32) + hist[0, 0, 0, 0].astype(jnp.int32) % F
                thr = jnp.full(n_nodes, B // 2, jnp.int32)
            if with_descend:
                feat_sel = table_select(feat, node, n_nodes)
                thr_sel = table_select(thr, node, n_nodes)
                f_iota = jnp.arange(bins_l.shape[1], dtype=jnp.int32)[None, :]
                row_bin = jnp.sum(
                    jnp.where(feat_sel[:, None] == f_iota,
                              bins_l.astype(jnp.int32), 0), axis=1)
                node = 2 * node + (row_bin > thr_sel).astype(jnp.int32)
            else:
                node = (node * 2) % (2 * n_nodes)
        leaf = -gsum / (hsum + 1.0) * 0.1
        if with_leaf:
            preds_new = preds_l + table_select(leaf, node, 64)
        else:
            preds_new = preds_l + leaf[0]
        return preds_new

    mapped = shard_map(round_body, mesh=mesh,
                       in_specs=(P("data", None), P("data"), P("data"), P("data")),
                       out_specs=P("data"), check_vma=False)
    return jax.jit(mapped, donate_argnums=(3,))


def timed(label, fn):
    p = fn(bins, y_d, w_d, jnp.copy(preds0))
    np.asarray(p)[:1]
    p = jnp.copy(preds0)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        p = fn(bins, y_d, w_d, p)
    _ = np.asarray(p)[:1]
    dt = (time.perf_counter() - t0) / ITERS
    print(f"{label:42s} {dt*1e3:9.1f} ms/round", flush=True)


timed("full round", make_round())
timed("no hist (split on zeros)", make_round(with_hist=False))
timed("no descend", make_round(with_descend=False))
timed("no split (fixed thr)", make_round(with_split=False))
timed("no leaf update", make_round(with_leaf=False))
timed("hist only (no split/descend/leaf)",
      make_round(with_split=False, with_descend=False, with_leaf=False))
