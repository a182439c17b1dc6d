#!/usr/bin/env python
"""GBLinear at out-of-core scale (VERDICT r3 #4 — the 50M×39 H2D story).

Streams the Criteo-shaped LibSVM page cache (shared with
bench_external.py) through ``GBLinear.fit_iter``: CSR pages densify into
a bounded staging slab and land on the chip via donated
``dynamic_update_slice`` writes — the full dense matrix NEVER exists on
the host — with ``feature_dtype=bfloat16`` (default here) halving both
the upload bytes and HBM residency (7.8 → 3.9 GB at 50M×39).

Reports one JSON line: assembly (stream+upload) seconds, boost rounds/s
with per-chunk evidence, peak host RSS.

    BENCH_GBLIN_ROWS=50000000 python scripts/bench_gblinear.py
    BENCH_GBLIN_DTYPE=float32  # f32 comparison run
"""
import json
import os
import resource
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = int(os.environ.get("BENCH_GBLIN_ROWS", 50_000_000))
FEATS = int(os.environ.get("BENCH_GBLIN_FEATURES", 39))
ROUNDS = int(os.environ.get("BENCH_GBLIN_ROUNDS", 50))
DTYPE = os.environ.get("BENCH_GBLIN_DTYPE", "bfloat16")
WORKDIR = os.environ.get("BENCH_EXT_DIR", "/tmp/dmlc_ext_bench")


def rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def main() -> None:
    os.makedirs(WORKDIR, exist_ok=True)
    svm = os.path.join(WORKDIR, f"criteo_{ROWS}x{FEATS}.svm")
    cache = os.path.join(WORKDIR, f"criteo_{ROWS}x{FEATS}.cache")
    gen = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "build", "gen_libsvm")
    out = {"rows": ROWS, "features": FEATS, "rounds": ROUNDS,
           "feature_dtype": DTYPE}

    if not os.path.exists(svm):
        t0 = time.perf_counter()
        subprocess.run([gen, str(ROWS), str(FEATS), svm, "7"], check=True,
                       stderr=subprocess.DEVNULL)
        out["gen_seconds"] = round(time.perf_counter() - t0, 1)

    from dmlc_core_tpu.data.iter import RowBlockIter
    from dmlc_core_tpu.models.linear import GBLinear

    t0 = time.perf_counter()
    it = RowBlockIter.create(f"{svm}#{cache}", 0, 1, "libsvm")
    out["open_or_parse_seconds"] = round(time.perf_counter() - t0, 1)

    m = GBLinear(n_rounds=ROUNDS, objective="binary:logistic",
                 feature_dtype=DTYPE)
    t0 = time.perf_counter()
    m.fit_iter(it, num_col=FEATS, warmup_rounds=3)
    total = time.perf_counter() - t0
    it.close()

    matrix_gb = ROWS * FEATS * (2 if DTYPE == "bfloat16" else 4) / 1e9
    out.update({
        "total_seconds": round(total, 1),
        "assembly_seconds": round(
            total - m.last_warmup_seconds - m.last_fit_seconds, 1),
        "matrix_gb_on_device": round(matrix_gb, 2),
        "assembly_mb_per_sec": round(matrix_gb * 1e3 / max(
            total - m.last_warmup_seconds - m.last_fit_seconds, 1e-9), 1),
        "warmup_seconds": round(m.last_warmup_seconds, 1),
        "boost_seconds": round(m.last_fit_seconds, 2),
        "rounds_per_sec": round(ROUNDS / m.last_fit_seconds, 2),
        "peak_rss_gb": round(rss_gb(), 2),
        "weight_norm": round(float((m.weights ** 2).sum() ** 0.5), 4),
        "bias": round(m.bias, 5),
    })
    from bench import chunk_stats
    out.update(chunk_stats(m.last_chunk_times, ROUNDS, m.last_fit_seconds))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
