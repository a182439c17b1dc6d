"""Sweep the histogram kernel's (lo, tile_rows) per tree level.

The _lo_factor chooser (ops/histogram.py) minimizes a construction-op
model 5A + 2lo calibrated on v5e at 4M rows; this sweep re-measures the
actual per-level cost at the north-star shape (10M rows) including
lo=256 (hi=1: LHS one-hot degenerates to the node plane) and a 16384 row
tile.  Slope timing over two scan lengths cancels the fixed
dispatch+fetch overhead (see profile_pieces.py).

Usage: ``ROWS=10000000 python scripts/sweep_hist.py``.

With ``CLASSES`` set (``CLASSES=7,3``) the sweep is over the CLASS axis
instead (ISSUE 49): at each ``n_build`` of ``NBUILDS`` one
``build_histogram`` of K classes ``[K, n]`` — the stacked calls of
``hist_class_blocks`` — against one call of a single class times K (the
K calls of a class loop are the same program on the same shapes), as ms
a build and us a feature's dots over one 16,384-row tile, all K classes
together.  Bins are uniform draws on the device (a dot's time does not
read the bins' values); one program a config, the trip count traced, so
the slope needs one compile.
``ROWS=9296192 FEATURES=54 CLASSES=7,3 python scripts/sweep_hist.py``.
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

from dmlc_core_tpu.ops import histogram as H
from dmlc_core_tpu.ops.quantile import apply_bins_t, compute_cuts

ROWS = int(os.environ.get("ROWS", 4_000_000))
F = int(os.environ.get("FEATURES", 28))
B = int(os.environ.get("BINS", 256))
DEPTH = int(os.environ.get("DEPTH", 6))
N1 = int(os.environ.get("N1", 5))
N2 = int(os.environ.get("N2", 25))
LOS = [int(x) for x in os.environ.get("LOS", "32,64,128,256").split(",")]
TILES = [int(x) for x in os.environ.get("TILES", "8192,16384").split(",")]

CLASSES = [int(x) for x in os.environ.get("CLASSES", "").split(",") if x]
NBUILDS = [int(x) for x in os.environ.get("NBUILDS", "1,2,4,8,16").split(",")]


def sweep_classes():
    key = jax.random.PRNGKey(0)
    bins_t = jax.random.randint(key, (F, ROWS), 0, B, jnp.int32).astype(
        jnp.uint8)
    kmax = max(CLASSES)
    g_all = jax.random.normal(jax.random.fold_in(key, 1), (kmax, ROWS))
    h_all = jnp.abs(g_all) + 0.1
    tiles = -(-ROWS // H._TILE_ROWS)

    def every(hist):
        # one cell of EVERY class: a build in several class blocks is
        # several kernel calls, and a result that reads the first
        # class alone lets the compiler drop the others
        return jnp.sum(hist[..., 0, 0, 0, 0]) * jnp.float32(1e-30)

    def slope(build, *args):
        @jax.jit
        def run(n, *a):
            return jax.lax.fori_loop(
                0, n, lambda _, c: every(build(a[0], a[1], a[2] + c, a[3])),
                jnp.float32(0.0))

        def once(n):
            t0 = time.perf_counter()
            np.asarray(run(n, *args))
            return time.perf_counter() - t0

        once(1)
        t1, t2 = once(N1), once(N2)
        return (t2 - t1) / (N2 - N1)

    results = {}
    for n_build in NBUILDS:
        full = jax.random.randint(jax.random.fold_in(key, 2 + n_build),
                                  (kmax, ROWS), 0, 2 * n_build, jnp.int32)
        # left children build, right children's rows are at -1 (the root
        # build, n_build = 1 with every row in it, costs the same)
        node = jnp.where(full % 2 == 0, full >> 1, -1)

        def build(b_t, nd, gg, hh, n_build=n_build):
            return H.build_histogram(b_t, nd, gg, hh, n_build, B, "pallas",
                                     transposed=True)

        one = slope(build, bins_t, node[0], g_all[0], h_all[0])
        results[f"n{n_build}/single"] = round(one * 1e3, 3)
        for k in CLASSES:
            dt = slope(build, bins_t, node[:k], g_all[:k], h_all[:k])
            blocks = H.hist_class_blocks(B, F, n_build, k)
            print(f"n_build={n_build:2d} K={k} blocks={blocks} "
                  f"lo={H._lo_stacked(n_build, B)} "
                  f"(single lo={H._lo_factor(n_build, B)}): "
                  f"stacked {dt*1e3:8.2f} ms = {dt*1e6/tiles/F:6.3f} us a "
                  f"feature-tile; {k} single calls {k*one*1e3:8.2f} ms = "
                  f"{k*one*1e6/tiles/F:6.3f} us ({one*1e6/tiles/F:.3f} a "
                  f"call); ratio {dt/(k*one):.3f}", flush=True)
            results[f"n{n_build}/K{k}"] = round(dt * 1e3, 3)
    print(json.dumps(results))


def tiny(x):
    return jnp.sum(x.ravel()[:4].astype(jnp.float32)) * jnp.float32(1e-30)


if CLASSES:
    sweep_classes()
    sys.exit(0)

rng = np.random.default_rng(0)
X = rng.normal(size=(ROWS, F)).astype(np.float32)
bins_t = apply_bins_t(jnp.asarray(X), compute_cuts(X, B))
g0 = jnp.asarray(rng.normal(size=ROWS).astype(np.float32))
h0 = jnp.abs(g0) + 0.1
np.asarray(bins_t[0, :1])


def slope(step, *args):
    @partial(jax.jit, static_argnums=(0,))
    def run(n, *a):
        return jax.lax.scan(lambda c, _: (step(c, *a), None),
                            jnp.float32(0.0), None, length=n)[0]

    def once(n):
        np.asarray(run(n, *args))
        t0 = time.perf_counter()
        np.asarray(run(n, *args))
        return time.perf_counter() - t0

    t1, t2 = once(N1), once(N2)
    return (t2 - t1) / (N2 - N1)


results = {}
for level in range(DEPTH):
    n_build = 1 if level == 0 else 1 << (level - 1)
    if level == 0:
        node_h = jnp.zeros(ROWS, jnp.int32)
    else:
        full = jnp.asarray(rng.integers(0, 2 * n_build, ROWS)
                           .astype(np.int32))
        node_h = jnp.where(full % 2 == 0, full >> 1, -1)
    cur = H._lo_factor(n_build, B)
    for lo in LOS:
        if lo > B:
            continue
        for tile in TILES:
            # _lo_factor inside _pallas_ok would override the swept lo;
            # check the swept config's own budget instead
            hi = -(-B // lo)
            nh = n_build * hi
            fp = -(-F // 8) * 8
            acc = fp * 2 * nh * max(lo, 128) * 4
            stack = tile * (fp + 120 + 6 * nh + 2 * lo)
            if acc > 24 << 20 or stack > 15 << 20:
                print(f"L{level} lo={lo} tile={tile}: skipped "
                      f"(vmem budget)", flush=True)
                continue

            def step(c, b_t, nh, gg, hh, lo=lo, tile=tile):
                out = H._hist_pallas(b_t, nh, gg + c, hh, n_build, B,
                                     tile, lo, True)
                return tiny(out)

            dt = slope(step, bins_t, node_h, g0, h0)
            tag = ("  <-- current"
                   if (lo == cur and tile == H._TILE_ROWS) else "")
            print(f"L{level} n_build={n_build:2d} lo={lo:3d} tile={tile:5d} "
                  f"{dt*1e3:9.2f} ms{tag}", flush=True)
            results[f"L{level}/lo{lo}/t{tile}"] = round(dt * 1e3, 3)
print(json.dumps(results))
