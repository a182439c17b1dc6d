#!/usr/bin/env python
"""Histogram-kernel CI drill (scripts/ci.sh stage).

Two halves, one JSON artifact (``CHECK_HIST_OUT``, default
``/tmp/hist_kernel.json``):

* **Cross-method parity sweep** — both histogram engines (segment /
  pallas-interpret) must produce the BIT-IDENTICAL
  ``[2, N, F, B]`` histogram on the same inputs, at odd row counts with
  masked (``node_id < 0``) rows, through an int4-packed
  :class:`~dmlc_core_tpu.ops.binlayout.BinLayout` (compact remap), and
  through a feature BUNDLE (unbundled via ``tot − Σseg``).  Gradients
  are drawn from {±1, ±0.5} and hessians from {0.5, 1} so every f32
  partial sum is exact regardless of reduction order — ``array_equal``
  is the assertion, not allclose.  Any mismatch fails the stage.
* **Timed micro-bench** — per-method ns/row on a jitted plain build and
  on the packed-layout build, archived so a kernel regression shows up
  as a number in the artifact chain rather than only as a slower BENCH
  headline.  Timing is evidence, never a gate (CPU CI timing is noisy;
  the bench owns the perf bar).

Knobs: ``CHECK_HIST_ROWS`` (micro-bench rows, default 50_000),
``CHECK_HIST_REPS`` (default 3).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dmlc_core_tpu.utils import force_cpu_devices  # noqa: E402

if os.environ.get("JAX_PLATFORMS", "") == "cpu":
    force_cpu_devices(1)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from dmlc_core_tpu.ops import binlayout as bl  # noqa: E402
from dmlc_core_tpu.ops.histogram import build_histogram  # noqa: E402

METHODS = ("segment", "pallas")


def _exact_gh(rng, n):
    """bf16-exact gradient/hessian draws: sums are exact in f32 for any
    reduction order, so cross-method comparisons can be bit-level."""
    g = rng.choice(np.array([-1.0, -0.5, 0.5, 1.0], np.float32), size=n)
    h = rng.choice(np.array([0.5, 1.0], np.float32), size=n)
    return g, h


def _node_ids(rng, n, n_nodes):
    nid = rng.integers(0, n_nodes, size=n).astype(np.int32)
    nid[rng.random(n) < 0.1] = -1          # masked rows contribute nothing
    return nid


def _spread_bins(rng, n, F, B, narrow):
    """[F, n] uint8 bins; ``narrow`` features use 2-6 SPREAD bin ids (the
    quantile-cut eps-bump shape that defeats width-based packing and
    requires the compact remap), the rest sweep all B bins."""
    rows = []
    for f in range(F):
        if f in narrow:
            k = 2 + (f % 5)
            ids = np.sort(rng.choice(B, size=k, replace=False))
            rows.append(ids[rng.integers(0, k, n)])
        else:
            rows.append((np.arange(n) + f) % B)
    return np.ascontiguousarray(np.stack(rows).astype(np.uint8))


def _exclusive_bins(rng, n, B):
    """3 features: one wide + two near-one-hot mutually exclusive ones
    (defaults 5 and 7; off-default rows never overlap) — the EFB shape."""
    onehot = rng.integers(0, 3, size=n)    # 0 = both default
    b0 = ((np.arange(n) * 7) % B).astype(np.uint8)
    b1 = np.where(onehot == 1, 20, 5).astype(np.uint8)
    b2 = np.where(onehot == 2, 25, 7).astype(np.uint8)
    return np.ascontiguousarray(np.stack([b0, b1, b2]))


def _build(bins_t, nid, g, h, n_nodes, n_bins, method, layout=None):
    fn = jax.jit(lambda b, i, gg, hh: build_histogram(
        b, i, gg, hh, n_nodes, n_bins, method, transposed=True,
        layout=layout))
    return np.asarray(fn(bins_t, nid, g, h))


def _parity_case(name, bins_t, layout, n_nodes, n_bins, rng):
    """All engines vs the plain segment reference; packed/bundled builds
    go through ``unbundle_hist`` back to ``[2, N, F, B]`` first."""
    n = bins_t.shape[1]
    g, h = _exact_gh(rng, n)
    nid = _node_ids(rng, n, n_nodes)
    ref = _build(bins_t, nid, g, h, n_nodes, n_bins, "segment")
    phys = (np.asarray(bl.pack_matrix(bins_t, layout))
            if layout is not None else None)
    mismatches = []
    for m in METHODS:
        if layout is None:
            got = _build(bins_t, nid, g, h, n_nodes, n_bins, m)
        else:
            st = _build(phys, nid, g, h, n_nodes, n_bins, m, layout=layout)
            got = np.asarray(bl.unbundle_hist(st, layout, n_bins))
        if not np.array_equal(got, ref):
            bad = int(np.sum(got != ref))
            mismatches.append(f"{m}: {bad} cells differ")
    return {"case": name, "rows": n, "methods": list(METHODS),
            "layout": (None if layout is None else
                       f"{layout.n_features}F->{layout.phys_rows}phys"),
            "ok": not mismatches, "mismatches": mismatches}


def _microbench(rows, reps):
    """Per-method ns/row on a jitted plain build (F=28, B=64, 8 nodes)
    plus the packed-layout pallas read path (28 narrow features -> 14
    int4 pairs).  Warm call excluded; median of ``reps`` timed calls."""
    F, B, n_nodes = 28, 64, 8
    rng = np.random.default_rng(3)
    g, h = _exact_gh(rng, rows)
    nid = _node_ids(rng, rows, n_nodes)
    out = {}

    def timed(tag, bins_t, method, layout=None):
        fn = jax.jit(lambda b, i, gg, hh: build_histogram(
            b, i, gg, hh, n_nodes, B, method, transposed=True,
            layout=layout))
        fn(bins_t, nid, g, h).block_until_ready()      # compile outside
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(bins_t, nid, g, h).block_until_ready()
            ts.append(time.perf_counter() - t0)
        out[tag] = round(sorted(ts)[len(ts) // 2] / rows * 1e9, 2)

    plain = _spread_bins(rng, rows, F, B, narrow=())
    for m in METHODS:
        timed(m, plain, m)
    narrow = _spread_bins(rng, rows, F, B, narrow=tuple(range(F)))
    layout = bl.compute_layout(bl.bin_counts(narrow, B), F, B, pack=True)
    if layout is not None:
        phys = np.asarray(bl.pack_matrix(narrow, layout))
        timed("pallas_packed", phys, "pallas", layout=layout)
    return out


def main() -> int:
    rng = np.random.default_rng(11)
    B = 32
    results = []

    # 1. plain engines, odd rows, masked nodes
    results.append(_parity_case(
        "plain_odd", _spread_bins(rng, 1021, 9, B, narrow=()), None, 4,
        B, rng))

    # 2. int4-packed compact-remap layout (narrow SPREAD bins)
    bins_n = _spread_bins(rng, 777, 9, B, narrow=(1, 4, 7, 8))
    lay_n = bl.compute_layout(bl.bin_counts(bins_n, B), 9, B, pack=True)
    assert lay_n is not None and lay_n.pairs, "packed layout must fire"
    results.append(_parity_case("packed_remap", bins_n, lay_n, 4, B, rng))

    # 3. feature bundle (mutually exclusive near-one-hot pair)
    bins_b = _exclusive_bins(rng, 1003, B)
    counts_b = bl.bin_counts(bins_b, B)
    bundles = bl.detect_bundles(bins_b, counts_b, B)
    assert bundles, "EFB detection must fire on the exclusive pair"
    lay_b = bl.compute_layout(counts_b, 3, B, pack=True, bundles=bundles)
    assert lay_b is not None and lay_b.has_bundles
    results.append(_parity_case("bundled", bins_b, lay_b, 2, B, rng))

    rows = int(os.environ.get("CHECK_HIST_ROWS", 50_000))
    reps = int(os.environ.get("CHECK_HIST_REPS", 3))
    t0 = time.perf_counter()
    ns_per_row = _microbench(rows, reps)
    record = {
        "check": "hist_kernel",
        "platform": jax.default_backend(),
        "parity": results,
        "microbench": {"rows": rows, "reps": reps,
                       "ns_per_row": ns_per_row,
                       "wall_s": round(time.perf_counter() - t0, 2)},
    }
    out_path = os.environ.get("CHECK_HIST_OUT", "/tmp/hist_kernel.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    print(json.dumps(record))

    bad = [r for r in results if not r["ok"]]
    if bad:
        print(f"FAIL: histogram engines disagree: {bad}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
