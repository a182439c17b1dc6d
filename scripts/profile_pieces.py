"""Trustworthy piecewise profile of one hist-GBT boosting round.

Mirrors the per-level structure of HistGBT's round body exactly
(models/histgbt.py round_body): level-0 full histogram, then per level
table_select ×2 + descend (select_feature_bins) + LEFT-child histogram
with n_build = 2^(l-1) (sibling subtraction), plus grad/hess, best-split
and the final descend + leaf update.  The sum of pieces is the
composition floor of one round; compare it against bench.py's measured
steady-state seconds/round to see what the fused round program gains
from XLA overlap, and against the cost-model floor (ops/histogram.py
_lo_factor docstring) to see how much the kernel loses to construction.

Timing method: a naive per-dispatch loop adds per-dispatch latency to
every piece, and some pieces are shorter than that latency.  Each piece
therefore runs as ONE
jitted ``lax.scan`` of N chained iterations (a scalar carry perturbs an
input each step so loop-invariant code motion cannot collapse the loop),
and the per-iteration time is the SLOPE between two run lengths:
``(t(N2) - t(N1)) / (N2 - N1)`` — fixed dispatch+fetch overhead cancels
exactly.

Output: one line per piece + a JSON summary (sum-of-pieces, hist-only
sum, implied attainable MFU at the bench's flop count).  Run on the TPU
chip: ``ROWS=10000000 python scripts/profile_pieces.py``.
"""
import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from dmlc_core_tpu.ops.histogram import (build_histogram, _lo_factor,
                                         select_feature_bins)
from dmlc_core_tpu.ops.quantile import apply_bins, compute_cuts

ROWS = int(os.environ.get("ROWS", 4_000_000))
F = int(os.environ.get("FEATURES", 28))
B = int(os.environ.get("BINS", 256))
DEPTH = int(os.environ.get("DEPTH", 6))
N1 = int(os.environ.get("N1", 5))
N2 = int(os.environ.get("N2", 25))

rng = np.random.default_rng(0)
X = rng.normal(size=(ROWS, F)).astype(np.float32)
bins = apply_bins(jnp.asarray(X), compute_cuts(X, B))
bins_t = jnp.asarray(np.asarray(bins).T)          # [F, n] — round layout
g0 = jnp.asarray(rng.normal(size=ROWS).astype(np.float32))
h0 = jnp.abs(g0) + 0.1
node_ids = {n: jnp.asarray(rng.integers(0, n, ROWS).astype(np.int32))
            for n in [1 << l for l in range(DEPTH)]}
np.asarray(bins_t[0, :1])  # sync upload


def timed(label, step, *args):
    """Per-iteration seconds of ``step(carry, *args) -> carry`` via the
    two-length scan slope.  ``step`` must consume its float carry (so the
    loop body is not invariant) and return a new small-float carry."""

    @partial(jax.jit, static_argnums=(0,))
    def run(n, *a):
        return jax.lax.scan(lambda c, _: (step(c, *a), None),
                            jnp.float32(0.0), None, length=n)[0]

    def once(n):
        out = run(n, *args)
        np.asarray(out)               # real fetch = proof of completion
        t0 = time.perf_counter()
        out = run(n, *args)
        np.asarray(out)
        return time.perf_counter() - t0

    t1, t2 = once(N1), once(N2)
    dt = (t2 - t1) / (N2 - N1)
    print(f"{label:52s} {dt*1e3:9.2f} ms", flush=True)
    return dt


def tiny(x):
    """Carry update: data-dependent but numerically inert (~1e-30)."""
    return jnp.sum(x.ravel()[:4].astype(jnp.float32)) * jnp.float32(1e-30)


pieces = {}

# --- grad/hess (logistic) --------------------------------------------
y = jnp.asarray((rng.random(ROWS) > 0.5).astype(np.float32))


def gh_step(c, yy):
    pred = jnp.full(ROWS, 0.1, jnp.float32) + c   # carry-dependent input
    p = jax.nn.sigmoid(pred)
    g = p - yy
    h = p * (1 - p)
    return tiny(g) + tiny(h)


pieces["grad_hess"] = timed("grad/hess", gh_step, y)


# --- histograms: level 0 full + levels 1..5 left-only ----------------
def hist_step(c, b_t, nh, gg, hh, n_build):
    out = build_histogram(b_t, nh, gg + c, hh, n_build, B, "pallas",
                          transposed=True)
    return tiny(out)


pieces["hist_L0"] = timed(
    f"hist L0 n_build=1 lo={_lo_factor(1, B)}",
    partial(hist_step, n_build=1),
    bins_t, jnp.zeros(ROWS, jnp.int32), g0, h0)

for level in range(1, DEPTH):
    n_prev = 1 << (level - 1)
    node_h = jnp.where(node_ids[2 * n_prev] % 2 == 0,
                       node_ids[2 * n_prev] >> 1, -1)
    pieces[f"hist_L{level}"] = timed(
        f"hist L{level} n_build={n_prev} lo={_lo_factor(n_prev, B)} "
        f"(left only)",
        partial(hist_step, n_build=n_prev),
        bins_t, node_h, g0, h0)


# --- descend: table_select x2 + row_bin + compare --------------------
def table_select(table, node, n_entries):
    n_iota = jnp.arange(n_entries, dtype=jnp.int32)[None, :]
    oh = node[:, None] == n_iota
    return jnp.sum(jnp.where(oh, table[None, :], 0), axis=1)


def descend_step(c, b_t, nd, n_prev):
    # carry perturbs the (tiny) threshold table — O(n_prev) extra work
    ft = jnp.zeros(n_prev, jnp.int32)
    tt = jnp.full(n_prev, B // 2, jnp.int32) + c.astype(jnp.int32)
    fs = table_select(ft, nd, n_prev)
    ts = table_select(tt, nd, n_prev)
    rb = select_feature_bins(b_t, fs)
    nd2 = 2 * nd + (rb > ts).astype(jnp.int32)
    return c * jnp.float32(0.5) + tiny(nd2)


for level in range(1, DEPTH):
    n_prev = 1 << (level - 1)
    pieces[f"descend_L{level}"] = timed(
        f"descend into L{level} (select x2 + row_bin + cmp)",
        partial(descend_step, n_prev=n_prev),
        bins_t, node_ids[n_prev])

# --- best split (all levels, tiny [2,N,F,B] reductions) --------------
from dmlc_core_tpu.models.histgbt import _make_best_split  # noqa: E402

bs = _make_best_split(B, 1.0, 0.0, 1.0)


def best_split_step(c):
    tot = c
    for level in range(DEPTH):
        n_nodes = 1 << level
        hist = jnp.full((2, n_nodes, F, B), 1.0, jnp.float32) + c
        f_, t_, gn = bs(hist, None)
        tot = tot + tiny(gn)
    return tot


pieces["best_split_all"] = timed("best_split all levels", best_split_step)

# --- final descend + leaf update -------------------------------------
half = 1 << (DEPTH - 1)


def final_step(c, b_t, nd):
    leaf = jnp.zeros(2 * half, jnp.float32) + c
    fs = table_select(jnp.zeros(half, jnp.int32), nd, half)
    ts = table_select(jnp.full(half, B // 2, jnp.int32), nd, half)
    rb = select_feature_bins(b_t, fs)
    nd2 = 2 * nd + (rb > ts).astype(jnp.int32)
    preds = jnp.zeros(ROWS, jnp.float32) + table_select(leaf, nd2, 2 * half)
    return tiny(preds)


pieces["final_leaf"] = timed("final descend + leaf update", final_step,
                             bins_t, node_ids[half])

# --- summary ----------------------------------------------------------
hist_sum = sum(v for k, v in pieces.items() if k.startswith("hist_"))
total = sum(pieces.values())
# same flop count bench.py reports (auditable cost model)
mxu_flops = 0
for level in range(DEPTH):
    n_build = 1 if level == 0 else 1 << (level - 1)
    lo = _lo_factor(n_build, B)
    hi = -(-B // lo)
    mxu_flops += 2 * (2 * n_build * hi) * lo * ROWS * F
peak = 197e12 if jax.default_backend() == "tpu" else 0
print("-" * 66)
summary = {
    "rows": ROWS,
    "sum_of_pieces_ms": round(total * 1e3, 2),
    "hist_pieces_ms": round(hist_sum * 1e3, 2),
    "non_hist_ms": round((total - hist_sum) * 1e3, 2),
    "mxu_flops_per_round": mxu_flops,
    "mfu_at_sum_of_pieces": round(mxu_flops / total / peak, 4) if peak else None,
    "mfu_if_hist_only": round(mxu_flops / hist_sum / peak, 4) if peak else None,
    "pieces_ms": {k: round(v * 1e3, 3) for k, v in pieces.items()},
}
print(json.dumps(summary))
