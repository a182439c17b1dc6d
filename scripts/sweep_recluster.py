"""Price the pieces of a leaf-wise tree's re-cluster on the chip.

A clustered leaf-wise round (``models/histgbt.py`` ``grow_tree_lossguide``,
``plan.recluster_at``) re-orders a device's rows by leaf ONCE a tree and
lets every later ``dmlc_hist`` build skip the tiles that hold none of its
node (``ops/histogram.py``: ``recluster_rows``, ``tile_liveness``,
``_hist_pallas_skip``).  This sweep, run by no cell, prices what that
rests on, at ``ROWS x FEATURES`` (24,000,000 x 28):

* ``PARTS=sort`` — the re-ordering's forms, each checked against numpy's
  stable argsort: ``variadic`` (ONE ``lax.sort``, one key, every payload
  an operand of its own: bin words, g, h, position), ``batched`` (the
  payloads stacked ``[W, n]`` and sorted along the rows against the key
  broadcast over them, two operands; ``unique``: the key made unique by
  the position, no stability asked), ``gather`` (sort (key, position)
  alone, then gather the payloads), the pack and unpack of the uint8
  matrix into uint32 words, the candidate-side pass (``select_feature_bins``
  and two ``table_select``), and the way back to input order (a
  two-operand sort by position against a scatter).  Compile seconds are
  printed beside the milliseconds: a sort compiles for ~15 s an operand.
* ``PARTS=tile`` — a dead tile: the shipped call over all-dead, 1/16-live
  and all-live rows against the plain ``_hist_pallas``, and the
  ``pl.when``-only fallback (every step names its own tile, so the sweep
  still streams the matrix).
* ``PARTS=round`` — whole rounds of the leaf-wise deployment (255 leaves,
  eta 0.1, ``min_child_weight`` 100, ``benchmark/datagen.higgs_like``
  rows) with the re-cluster at each of ``POINTS`` (``8;16;32;8,64``; an
  empty entry is today's one scan), with and without the candidate-side
  bit, ``ROUNDS`` rounds a fit, the second fit timed.

Usage: ``chiprun --timeout 3000 -- env PARTS=sort,tile,round python
scripts/sweep_recluster.py``; the table goes to
``chiprun_out/sweep_recluster.json``.
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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dmlc_core_tpu.models import histgbt as G
from dmlc_core_tpu.ops import histogram as H
from dmlc_core_tpu.ops.table_select import table_select

ROWS = int(os.environ.get("ROWS", "24000000"))
FEATURES = int(os.environ.get("FEATURES", "28"))
PARTS = os.environ.get("PARTS", "sort,tile,round").split(",")
POINTS = [tuple(int(v) for v in p.split(",") if v)
          for p in os.environ.get("POINTS", ";8;16;32;8,64").split(";")]
ROUNDS = int(os.environ.get("ROUNDS", "2"))
CLUSTERS = 34                 # 17 open leaves x the candidate's two sides
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "chiprun_out")
results = {}


def timed(name, fn, *args, calls=3):
    """Compile seconds of ``jit(fn)`` and the best of ``calls`` runs."""
    t0 = time.perf_counter()
    exe = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    out = jax.block_until_ready(exe(*args))
    best = None
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(exe(*args))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    results[name] = {"ms": round(best * 1e3, 3),
                     "compile_s": round(compile_s, 2)}
    print(f"{name:42s} {best * 1e3:10.3f} ms   compile {compile_s:7.2f} s",
          flush=True)
    return out


@partial(jax.jit, static_argnums=(1, 2))
def random_bins(key, rows, n):
    return jax.random.randint(key, (rows, n), 0, 256,
                              jnp.int32).astype(jnp.uint8)


tile_aligned = jax.jit(H.tile_aligned)


def say(name, **kw):
    results[name] = kw
    print(f"{name:42s} {kw}", flush=True)


# ---- the forms of the re-ordering -------------------------------------------

def pack_words(bins_t):
    # the ``reshape`` forms of the pack and the unpack, kept here as the
    # comparison: at 24M rows they compile for 113 and 303 s on the
    # chip's host (PR 57); ``H.recluster_rows`` ships them row by row
    W = -(-FEATURES // 4)
    q = bins_t[:4 * W].reshape(W, 4, -1).astype(jnp.uint32)
    return q[:, 0] | (q[:, 1] << 8) | (q[:, 2] << 16) | (q[:, 3] << 24)


def unpack_words(words, Fp):
    q = jnp.stack([(words >> s) & 255 for s in (0, 8, 16, 24)], axis=1)
    b = q.astype(jnp.uint8).reshape(4 * words.shape[0], -1)
    return jnp.pad(b, ((0, Fp - b.shape[0]), (0, 0)))


def as_u32(x):
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def variadic(key, words, g, h, order, stable=True):
    return jax.lax.sort((key, *words, g, h, order), num_keys=1,
                        is_stable=stable)


def batched(key, payload, stable=True):
    """``payload`` ``[W, n]`` uint32 in the order of ``key``."""
    keys = jnp.broadcast_to(key, payload.shape)
    return jax.lax.sort((keys, payload), dimension=1, num_keys=1,
                        is_stable=stable)[1]


def unique_key(key, n_pad):
    bits = max(int(n_pad - 1).bit_length(), 1)
    assert CLUSTERS < (1 << (31 - bits)), "key and position pass 31 bits"
    pos = jnp.arange(n_pad, dtype=jnp.int32)
    return (jnp.minimum(key, CLUSTERS) << bits) | pos, (1 << bits) - 1


def gathered(key, payload, order):
    _, perm = jax.lax.sort((key, order), num_keys=1, is_stable=True)
    return jnp.take(payload, perm, axis=1), perm


def part_sort():
    rng = np.random.default_rng(0)
    n_pad = -(-ROWS // H._TILE_ROWS) * H._TILE_ROWS
    Fp = -(-FEATURES // 8) * 8
    key_np = rng.integers(0, CLUSTERS, n_pad).astype(np.int32)
    key_np[ROWS:] = np.iinfo(np.int32).max
    perm_np = np.argsort(key_np, kind="stable").astype(np.int32)
    key = jnp.asarray(key_np)
    bins = random_bins(jax.random.key(1), Fp, n_pad)
    g = jax.random.normal(jax.random.key(2), (n_pad,), jnp.float32)
    h = jax.random.uniform(jax.random.key(3), (n_pad,), jnp.float32)
    order = jnp.arange(n_pad, dtype=jnp.int32)

    words = timed("pack u8[Fp,n] -> u32 words", pack_words, bins)
    timed("unpack u32 words -> u8[Fp,n]", partial(unpack_words, Fp=Fp),
          words)
    W = words.shape[0]
    want = np.asarray(jnp.take(words[0], jnp.asarray(perm_np)))

    def check(name, got_word0, got_order=None):
        ok = bool(np.array_equal(np.asarray(got_word0), want))
        if got_order is not None:
            ok = ok and bool(np.array_equal(np.asarray(got_order), perm_np))
        results[name]["exact"] = ok
        if not ok:
            print(f"{name}: WRONG", flush=True)

    for stable in (True, False):
        name = f"variadic {W + 4} operands stable={stable}"
        out = timed(name, partial(variadic, stable=stable), key,
                    tuple(words), g, h, order)
        if stable:
            check(name, out[1], out[-1])
        del out
    payload = jnp.concatenate([words, as_u32(g)[None], as_u32(h)[None],
                               as_u32(order)[None]])
    for rows in (W + 3, 8, 16):
        pl_ = (payload[:rows] if rows <= payload.shape[0] else jnp.pad(
            payload, ((0, rows - payload.shape[0]), (0, 0))))
        name = f"batched [{rows},n] stable"
        out = timed(name, batched, key, pl_)
        check(name, out[0])
        ukey, _ = unique_key(key, n_pad)
        name = f"batched [{rows},n] unique key"
        out = timed(name, partial(batched, stable=False), ukey, pl_)
        check(name, out[0])
        del out, pl_
    name = f"gather: sort(key, pos) + take [{W + 2},n]"
    out, perm = timed(name, gathered, key, payload[:W + 2], order)
    check(name, out[0], perm)
    del out
    timed("sort(key, pos) alone, stable", lambda k, o: jax.lax.sort(
        (k, o), num_keys=1, is_stable=True), key, order)
    # the way back: node in the input's order
    perm = jnp.asarray(perm_np)
    node = jnp.asarray(np.where(key_np[perm_np] < CLUSTERS,
                                key_np[perm_np], -1).astype(np.int32))
    back = timed("unsort: sort(pos, node)", lambda o, nd: jax.lax.sort(
        (o, nd), num_keys=1, is_stable=False)[1], perm, node)
    back2 = timed("unsort: scatter by pos", lambda o, nd: jnp.zeros_like(
        nd).at[o].set(nd, unique_indices=True), perm, node)
    say("unsort forms agree",
        exact=bool(np.array_equal(np.asarray(back), np.asarray(back2))))
    # the candidate-side pass: two lookups of 33 entries and the select
    tab = jnp.asarray(rng.integers(0, FEATURES, 33).astype(np.int32))
    node33 = jnp.asarray(rng.integers(0, 33, n_pad).astype(np.int32))
    timed("side: 2 table_select(33) + select_feature_bins",
          lambda b, t, nd: H.select_feature_bins(
              b, table_select(t, nd, 33)) > table_select(t, nd, 33),
          bins, tab, node33)
    timed("shipped recluster_rows", lambda k, b, g_, h_, o:
          H.recluster_rows(k, b, FEATURES, g_, h_, o), key, bins, g, h,
          order)


# ---- a dead tile ---------------------------------------------------------------

def own_tile_call(bins, node, g, h, live):
    """The ``pl.when``-only fallback: the shipped kernel, every step's
    blocks its own tile's (``src = arange``)."""
    Fp, n_pad = bins.shape
    grid = live.shape[0]
    T = n_pad // grid
    lo = H._lo_factor(1, 256)
    hi = 256 // lo
    rows = pl.BlockSpec((1, T), lambda i, live, src: (0, src[i]))
    return pl.pallas_call(
        partial(H._hist_pallas_skip_kernel, n_nodes=1, hi=hi, lo=lo,
                n_rows=FEATURES),
        out_shape=jax.ShapeDtypeStruct((Fp, 2 * hi, lo), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(grid,),
            in_specs=[pl.BlockSpec((Fp, T),
                                   lambda i, live, src: (0, src[i])),
                      rows, rows, rows],
            out_specs=pl.BlockSpec((Fp, 2 * hi, lo),
                                   lambda i, live, src: (0, 0, 0))),
        interpret=H.pallas_interpret(), name="dmlc_hist",
    )(live, jnp.arange(grid, dtype=jnp.int32), bins,
      node.reshape(1, n_pad), g.reshape(1, n_pad), h.reshape(1, n_pad))


def part_tile():
    n = ROWS
    bins_t = random_bins(jax.random.key(1), FEATURES, n)
    g = jax.random.normal(jax.random.key(2), (n,), jnp.float32)
    h = jax.random.uniform(jax.random.key(3), (n,), jnp.float32)
    zero = jnp.zeros(n, jnp.int32)
    plain = timed("plain _hist_pallas, all rows", lambda b, nd, g_, h_:
                  H.build_histogram(b, nd, g_, h_, 1, 256, "pallas",
                                    transposed=True), bins_t, zero, g, h)
    bins, node, gp, hp = tile_aligned(bins_t, zero, g, h)
    grid = node.shape[0] // H._TILE_ROWS

    def skip(b, nd, g_, h_):
        return H.build_histogram(b, nd, g_, h_, 1, 256, "pallas",
                                 transposed=True, n_features=FEATURES,
                                 tile_live=H.tile_liveness(nd))

    full = timed("skip call, all tiles live", skip, bins, node, gp, hp)
    say("skip call == plain call, all live",
        exact=bool(np.array_equal(np.asarray(full), np.asarray(plain))))
    for share in (16, 64):
        part = jnp.where(jnp.arange(node.shape[0]) // H._TILE_ROWS
                         < grid // share, node, -1)
        timed(f"skip call, first 1/{share} of the tiles live", skip, bins,
              part, gp, hp)
    dead = jnp.full_like(node, -1)
    timed("skip call, all tiles dead", skip, bins, dead, gp, hp)
    timed("pl.when only (own tiles), all dead", lambda b, nd, g_, h_:
          own_tile_call(b, nd, g_, h_, H.tile_liveness(nd)), bins, dead, gp,
          hp)
    timed("tile_liveness alone", H.tile_liveness, node)
    say("grid", tiles=grid, tile_rows=H._TILE_ROWS)


# ---- whole rounds ----------------------------------------------------------------

def part_round():
    from benchmark import datagen
    from dmlc_core_tpu.models import HistGBT
    from dmlc_core_tpu.parallel.mesh import local_mesh

    X, y = datagen.higgs_like(ROWS, FEATURES, 4000000057)
    kw = dict(n_trees=ROUNDS, mesh=local_mesh(1), grow_policy="lossguide",
              max_leaves=255, max_depth=0, n_bins=256, learning_rate=0.1,
              min_child_weight=100.0, objective="binary:logistic",
              base_score=0.0)
    points_fn, select = H.recluster_points, G.select_feature_bins
    cases = [(pts, side) for pts in POINTS
             for side in ((True, False) if pts else (True,))]

    def engage(points, side):
        H.recluster_points = G.recluster_points = (
            lambda leaves, rows, features: points)
        # without the side bit: every row reads bin 0 of its leaf's
        # candidate feature, so no row goes right
        G.select_feature_bins = select if side else (
            lambda b, f, layout=None: jnp.zeros(f.shape, jnp.int32))
        G._ROUND_FN_CACHE.clear()
        G._AOT_EXEC_CACHE.clear()

    # the handle's background compile is the first case's program
    engage(*cases[0])
    model = HistGBT(**kw)
    handle = model.make_device_data(X, y)
    del X
    base = None
    for i, (points, side) in enumerate(cases):
        engage(points, side)
        m = model if i == 0 else HistGBT(**kw)
        t0 = time.perf_counter()
        m.fit_device(handle)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        m.fit_device(handle)
        wall = time.perf_counter() - t0
        trees = m.trees
        if base is None:
            base = trees
        same = all(np.array_equal(a[k], b[k]) for a, b in zip(
            base, trees) for k in ("feat", "thr", "left", "right"))
        value_gap = max(float(np.max(np.abs(a["value"] - b["value"])))
                        for a, b in zip(base, trees))
        say(f"round at {list(points)} side={side}",
            s_per_round=round(wall / ROUNDS, 4),
            first_fit_s=round(first, 2),
            rows_per_build=m.round_plan["hist_rows_per_build"],
            splits_equal=same, value_gap=value_gap)
    H.recluster_points = G.recluster_points = points_fn
    G.select_feature_bins = select


dev = jax.devices()[0]
print(f"device {dev.platform} {dev.device_kind}; rows {ROWS} x {FEATURES}",
      flush=True)
for part in PARTS:
    {"sort": part_sort, "tile": part_tile, "round": part_round}[part]()
os.makedirs(OUT, exist_ok=True)
with open(os.path.join(OUT, "sweep_recluster.json"), "w") as f:
    json.dump({"device": dev.device_kind, "rows": ROWS,
               "features": FEATURES, "results": results}, f, indent=1)
