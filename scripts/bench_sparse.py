"""SparseHistGBT bench: synthetic 100k-feature sparse LibSVM workload.

BASELINE config 3's "sparse CSR" seam at its natural scale (VERDICT r4
missing #2): bag-of-words-shaped data — F = 100k, density 0.5% — where
the dense engine's [n, F] bin matrix is impossible (n·F = 10^10 cells)
and the ragged sparse path touches only the nnz present entries.

Prints one JSON line: rows/features/nnz/total_bins, fit seconds,
rounds/s, train accuracy (sanity: the engine must actually learn), and
the predict pass rate.  Env knobs: SPARSE_ROWS (1e5), SPARSE_F (1e5),
SPARSE_DENSITY (0.005), SPARSE_ROUNDS (20), SPARSE_BINS (32),
SPARSE_DEPTH (6), BENCH_CPU=1 to force the virtual-CPU backend.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("BENCH_CPU"):
    from dmlc_core_tpu.utils import force_cpu_devices
    force_cpu_devices(1)

import numpy as np  # noqa: E402


def main():
    n = int(float(os.environ.get("SPARSE_ROWS", 100_000)))
    F = int(float(os.environ.get("SPARSE_F", 100_000)))
    density = float(os.environ.get("SPARSE_DENSITY", 0.005))
    rounds = int(os.environ.get("SPARSE_ROUNDS", 20))
    n_bins = int(os.environ.get("SPARSE_BINS", 32))
    depth = int(os.environ.get("SPARSE_DEPTH", 6))
    nnz_per_row = max(2, int(F * density))

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    # power-law feature popularity (bag-of-words shape): stop-word
    # features plus a long tail; features 0/1 carry the label and are
    # present in every row; duplicates within a row are dropped (the
    # engine rejects them — one entry per (row, feature))
    pop = 1.0 / np.arange(1, F - 1) ** 0.7
    pop /= pop.sum()
    draw = rng.choice(F - 2, size=(n, nnz_per_row - 2), p=pop) + 2
    draw.sort(axis=1)
    first = np.concatenate([np.ones((n, 1), bool),
                            draw[:, 1:] != draw[:, :-1]], axis=1)
    sel_idx = draw[first].astype(np.int64)
    sel_val = rng.normal(size=len(sel_idx)).astype(np.float32)
    counts = first.sum(axis=1)
    offset = np.concatenate([[0], np.cumsum(counts + 2)]).astype(np.int64)
    total = int(offset[-1])
    v0 = rng.normal(size=n).astype(np.float32)
    v1 = rng.normal(size=n).astype(np.float32)
    index = np.empty(total, np.int64)
    value = np.empty(total, np.float32)
    starts = offset[:-1]
    index[starts] = 0
    index[starts + 1] = 1
    value[starts] = v0
    value[starts + 1] = v1
    rows_sel = np.repeat(np.arange(n), counts)
    rank = (np.arange(len(sel_idx))
            - np.repeat(np.concatenate([[0], np.cumsum(counts)[:-1]]),
                        counts))
    pos = starts[rows_sel] + 2 + rank
    index[pos] = sel_idx
    value[pos] = sel_val
    y = (v0 + 0.5 * v1 > 0).astype(np.float32)
    gen_s = time.perf_counter() - t0

    from dmlc_core_tpu.models.histgbt_sparse import SparseHistGBT

    kw = dict(max_depth=depth, n_bins=n_bins, learning_rate=0.3)
    # warmup fit: compiles the k-round chunk program so the timed fit
    # below measures steady state, not compilation.  Must run the SAME
    # rounds-per-dispatch k as the timed fit — a 1-tree warmup compiles
    # only the k=1 program and the timed fit then pays the k=8 compile
    # inside its wall (measured: 74 s for 40 rounds vs 21 s warm).
    K = int(os.environ.get("DMLC_TPU_SPARSE_ROUNDS_PER_DISPATCH", "8"))
    t0 = time.perf_counter()
    SparseHistGBT(n_trees=min(rounds, K), **kw).fit(
        offset, index, value, y, n_features=F)
    if rounds > K and rounds % K:
        # the tail chunk is its own k (static argname → own program);
        # compile it here or it lands inside the timed fit
        SparseHistGBT(n_trees=rounds % K, **kw).fit(
            offset, index, value, y, n_features=F)
    warmup_s = time.perf_counter() - t0
    m = SparseHistGBT(n_trees=rounds, **kw)
    t0 = time.perf_counter()
    m.fit(offset, index, value, y, n_features=F)
    fit_s = time.perf_counter() - t0
    pred = m.predict(offset, index, value)       # compiles the scan
    t0 = time.perf_counter()
    pred = m.predict(offset, index, value)
    pred_s = time.perf_counter() - t0
    acc = float(((pred > 0.5) == y).mean())

    import jax
    out = {
        "metric": "sparse_histgbt_rounds_per_sec",
        "value": round(rounds / fit_s, 4),
        "unit": "rounds/s",
        "rows": n, "features": F, "nnz": int(offset[-1]),
        "density": round(float(offset[-1]) / (n * F), 5),
        "total_bins": m.cuts.total_bins,
        "dense_bins_would_be": F * n_bins,
        "n_bins": n_bins, "depth": depth, "rounds": rounds,
        "gen_seconds": round(gen_s, 2),
        "warmup_seconds": round(warmup_s, 2),
        "fit_seconds": round(fit_s, 2),
        "predict_seconds": round(pred_s, 2),
        "train_acc": round(acc, 4),
        "platform": jax.devices()[0].platform,
    }
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
