#!/usr/bin/env python
"""Timeboxed spike (VERDICT r3 #5): attack the L0-L2 histogram floor.

The shipped Pallas kernel is near-roofline at deep levels but pinned at
~9-11 ms/level for L0-L2 (13-21% MXU) by per-feature fixed work that
does not scale with A·lo.  This spike slope-times kernel VARIANTS on
the real chip to (a) attribute the floor among {construction, dot,
accumulate}, (b) test the one untried structural change that is not a
documented dead end: batching each 8-feature group's output
accumulation into one VMEM-carried write (the shipped kernel does a
sublane-padded [1, A, lo] read-modify-write per feature — 8× padded
traffic on the out block).

Documented dead ends NOT re-derived here (BASELINE.md roofline,
memory): subtile packing, fused descend, lo=256, tile 32768/65536,
per-page... Slope method: each timing chains N level-passes inside one
jitted lax.scan with a carry perturbation, two N values cancel the
fixed dispatch overhead exactly.

Usage:  python scripts/spike_hist_floor.py   (on the TPU)
        SPIKE_ROWS=2000000 python scripts/spike_hist_floor.py
"""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from dmlc_core_tpu.ops.histogram import (  # noqa: E402
    _TILE_ROWS, _lo_factor)

ROWS = int(os.environ.get("SPIKE_ROWS", 10_000_000))
FEATS = int(os.environ.get("SPIKE_FEATURES", 28))
BINS = 256


def _prep(n_build):
    rng = np.random.default_rng(0)
    bins_t = jnp.asarray(rng.integers(0, BINS, size=(FEATS, ROWS),
                                      dtype=np.uint8))
    node = jnp.asarray(
        rng.integers(0, max(2 * n_build, 1), size=ROWS, dtype=np.int32))
    g = jnp.asarray(rng.normal(size=ROWS).astype(np.float32))
    h = jnp.asarray(rng.random(ROWS).astype(np.float32))
    return bins_t, node, g, h


def _kernel_variant(bins_ref, node_ref, g_ref, h_ref, out_ref, *,
                    n_nodes, hi, lo, variant):
    """Variants of the shipped factored kernel's inner loop.

    shipped   — per-feature [1, A, lo] out accumulate (baseline copy)
    grpacc    — carry the 8-feature group's [8, A, lo] result in VMEM
                values, ONE out write per group
    nodot     — construction only (dot replaced by a cheap reduce) to
                attribute construction vs MXU cost
    noconstr  — dot on REUSED one-hots (construction hoisted out of the
                per-feature loop; wrong results, timing only)
    pack4/8   — r5, the "bin-packed dot" half of VERDICT r3 #5: S
                features share ONE dot ([S·2nh, T]·[S·lo, T] → the
                [2nh, lo] diagonal blocks are the per-feature results,
                cross-feature off-diagonals discarded).  A lo=32 dot
                pads 32 → 128 RHS lanes; packing fills those lanes
                with real work and cuts per-tile dot issues S×.  (The
                int8-MXU half of r3 #5 is analytically out: the LHS
                carries f32 g/h scaling — an int8×int8 dot can only
                COUNT, and the histogram needs weighted sums; also
                Mosaic rejects sub-int32 vector compares on this
                target, so int8 one-hot construction has no path
                either.)
    """
    i = pl.program_id(0)
    node = node_ref[:].astype(jnp.int32)
    g = g_ref[:].astype(jnp.bfloat16)
    h = h_ref[:].astype(jnp.bfloat16)
    F, T = bins_ref.shape
    nh = n_nodes * hi

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    nh_iota = jax.lax.broadcasted_iota(jnp.int32, (nh, T), 0)
    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (lo, T), 0)
    valid = node >= 0
    t0_node = jnp.where(valid, jnp.where(valid, node, 0) * hi,
                        jnp.int32(-(1 << 20)))

    oh0 = (nh_iota == t0_node).astype(jnp.bfloat16)        # for noconstr
    lhs0 = jnp.concatenate([oh0 * g, oh0 * h], axis=0)
    rhs0 = (lo_iota == 0).astype(jnp.bfloat16)

    def body(fg, carry):
        base = pl.multiple_of(fg * 8, 8)
        blk = bins_ref[pl.ds(base, 8), :].astype(jnp.int32)
        t0s = t0_node + blk // lo
        los = blk % lo
        if variant in ("pack4", "pack8"):
            S = int(variant[4:])
            for j in range(8 // S):
                lhss, rhss = [], []
                for k in range(S):
                    kk = S * j + k
                    oh = (nh_iota == t0s[kk:kk + 1]).astype(jnp.bfloat16)
                    lhss.append(jnp.concatenate([oh * g, oh * h], axis=0))
                    rhss.append((lo_iota == los[kk:kk + 1])
                                .astype(jnp.bfloat16))
                d = jax.lax.dot_general(
                    jnp.concatenate(lhss, axis=0),
                    jnp.concatenate(rhss, axis=0),
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [S·2nh, S·lo]
                acc = jnp.stack(
                    [d[k * 2 * nh:(k + 1) * 2 * nh,
                       k * lo:(k + 1) * lo] for k in range(S)], axis=0)
                idx = (pl.ds(base + S * j, S), slice(None), slice(None))
                out_ref[idx] = out_ref[idx] + acc
            return carry
        if variant == "grpacc":
            # ONE [8, 2nh, lo] write per feature group instead of 8
            # sublane-padded [1, ...] read-modify-writes.  jnp.stack of
            # the statically-unrolled dots (scatter .at[].set does not
            # lower in Mosaic)
            ds = []
            for k in range(8):
                oh = (nh_iota == t0s[k:k + 1]).astype(jnp.bfloat16)
                lhs = jnp.concatenate([oh * g, oh * h], axis=0)
                rhs = (lo_iota == los[k:k + 1]).astype(jnp.bfloat16)
                ds.append(jax.lax.dot_general(
                    lhs, rhs, dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32))
            acc = jnp.stack(ds, axis=0)
            idx = (pl.ds(base, 8), slice(None), slice(None))
            out_ref[idx] = out_ref[idx] + acc
            return carry
        for k in range(8):
            if variant == "noconstr":
                lhs, rhs = lhs0, rhs0
            else:
                oh = (nh_iota == t0s[k:k + 1]).astype(jnp.bfloat16)
                lhs = jnp.concatenate([oh * g, oh * h], axis=0)
                rhs = (lo_iota == los[k:k + 1]).astype(jnp.bfloat16)
            if variant == "nodot":
                d = (jnp.sum(lhs, axis=1, keepdims=True)
                     + jnp.sum(rhs, axis=1, keepdims=True)[: 2 * nh]
                     ) * jnp.ones((2 * nh, lo), jnp.float32)
            else:
                d = jax.lax.dot_general(
                    lhs, rhs, dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            idx = (pl.ds(fg * 8 + k, 1), slice(None), slice(None))
            out_ref[idx] = out_ref[idx] + d[None]
        return carry

    jax.lax.fori_loop(0, F // 8, body, 0)


def _run_level(bins_t, node, g, h, n_build, variant):
    lo = _lo_factor(n_build, BINS)
    hi = -(-BINS // lo)
    T = _TILE_ROWS
    n = bins_t.shape[1]
    grid = n // T
    kern = functools.partial(_kernel_variant, n_nodes=n_build, hi=hi,
                             lo=lo, variant=variant)
    fp = FEATS - FEATS % 8  # keep it simple: multiple-of-8 features only

    def one_pass(bins_t, node, g, h):
        if variant == "prod":
            from dmlc_core_tpu.ops.histogram import build_histogram
            return build_histogram(bins_t[:fp], node, g, h,
                                   n_build, BINS, "pallas",
                                   transposed=True)
        return pl.pallas_call(
            kern,
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((fp, T), lambda i: (0, i)),
                pl.BlockSpec((1, T), lambda i: (0, i)),
                pl.BlockSpec((1, T), lambda i: (0, i)),
                pl.BlockSpec((1, T), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((fp, 2 * n_build * hi, lo),
                                   lambda i: (0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((fp, 2 * n_build * hi, lo),
                                           jnp.float32),
        )(bins_t[:fp], node[None], g[None], h[None])

    @functools.partial(jax.jit, static_argnums=(4,))
    def chain(bins_t, node, g, h, reps):
        def step(carry, _):
            # perturb the node input from the carry so LICM cannot
            # collapse the chain to one pass
            out = one_pass(bins_t, jnp.bitwise_and(
                node + carry.astype(jnp.int32)[:1], 0x7fffffff) % max(
                2 * n_build, 1), g, h)
            return out.reshape(-1)[:1].astype(jnp.float32), None

        c, _ = jax.lax.scan(step, jnp.zeros(1, jnp.float32), None,
                            length=reps)
        return c

    def timed(reps):
        t0 = time.perf_counter()
        np.asarray(chain(bins_t, node, g, h, reps))
        return time.perf_counter() - t0

    timed(2)                       # compile both
    timed(12)
    slopes = []
    for _ in range(3):             # median of 3: single slopes
        t_small, t_big = timed(4), timed(24)   # swing run to run
        slopes.append((t_big - t_small) / 20.0)
    return sorted(slopes)[1]


def main():
    out = {"rows": ROWS, "features": FEATS, "tile": _TILE_ROWS,
           "platform": jax.devices()[0].platform}
    for n_build in (1, 2):               # the L0-L2 floor levels
        bins_t, node, g, h = _prep(n_build)
        for variant in ("prod", "shipped", "pack4", "pack8"):
            try:
                ms = _run_level(bins_t, node, g, h, n_build, variant) * 1e3
                out[f"nb{n_build}_{variant}_ms"] = round(ms, 3)
            except Exception as e:  # noqa: BLE001
                out[f"nb{n_build}_{variant}_ms"] = (
                    f"FAIL {type(e).__name__}: {e}"[:120])
            print(json.dumps({k: out[k] for k in list(out)[-1:]}),
                  flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
