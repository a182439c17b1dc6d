#!/usr/bin/env python
"""Multi-chip sharded-ingest parity drill (scripts/ci.sh stage).

Proves, on an 8-device CPU mesh (the tier-1 stand-in for a v5e-8
slice), the three bit-parity contracts of the multi-chip HistGBT data
plane — then archives the evidence as a JSON scaling report (the
CPU-side counterpart of the ``MULTICHIP_r0*.json`` artifacts):

1. **1-chip oracle** — with the deterministic histogram reduction
   (``DMLC_HIST_BLOCKS``), an 8-chip data-parallel fit of the same
   global rows serializes (``save_model``) byte-identically to the
   1-chip fit: sharding changed WHERE rows live, not what was learned.
2. **Sharded ingest** — per-chip slab staging produces a binned matrix
   and ensemble byte-identical to the global-put path on the same mesh
   (odd row count: the last-shard remainder and chunk-tail math).
3. **Out-of-core** — the same rows streamed through
   ``make_device_data_iter`` in tiny ``DMLC_INGEST_CHUNK_ROWS`` slabs
   (DiskRowIter-shaped source, full matrix never materialized) still
   match byte-identically.

Exit 0 = all parities hold; the report lands at ``--out`` /
``MULTICHIP_OUT`` (default /tmp/multichip_scaling.json).
"""

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_DEV = int(os.environ.get("MULTICHIP_DEVICES", 8))
os.environ["DMLC_HIST_BLOCKS"] = os.environ.get("DMLC_HIST_BLOCKS",
                                                str(N_DEV))

from dmlc_core_tpu.utils import force_cpu_devices  # noqa: E402

force_cpu_devices(N_DEV)

import numpy as np  # noqa: E402


def _save_bytes(model) -> bytes:
    path = tempfile.mktemp(suffix=".gbt")
    try:
        model.save_model(path)
        with open(path, "rb") as f:
            return f.read()
    finally:
        if os.path.exists(path):
            os.remove(path)


def _trees_equal(a, b) -> bool:
    return (len(a.trees) == len(b.trees)
            and all(np.array_equal(ta[k], tb[k])
                    for ta, tb in zip(a.trees, b.trees) for k in ta))


def main() -> int:
    out_path = os.environ.get("MULTICHIP_OUT", "/tmp/multichip_scaling.json")
    for i, a in enumerate(sys.argv):
        if a == "--out" and i + 1 < len(sys.argv):
            out_path = sys.argv[i + 1]

    import jax
    from jax.sharding import Mesh

    from dmlc_core_tpu.models import HistGBT
    from dmlc_core_tpu.ops.histogram import hist_psum_bytes_per_round
    from dmlc_core_tpu.ops.quantile import compute_cuts

    devs = np.array(jax.devices())
    assert len(devs) >= N_DEV, (len(devs), N_DEV)

    rng = np.random.default_rng(7)
    n, F = 10_007, 12                    # odd: remainder/tail paths live
    depth, n_bins, rounds = 4, 32, 6
    X = rng.normal(size=(n, F)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + 0.5 * X[:, 2] > 0).astype(np.float32)
    cuts = compute_cuts(X, n_bins)
    kw = dict(n_trees=rounds, max_depth=depth, n_bins=n_bins,
              learning_rate=0.3)

    report = {"check": "multichip_scaling", "n_devices": N_DEV,
              "rows": n, "features": F, "rounds": rounds,
              "deterministic_hist_blocks":
                  int(os.environ["DMLC_HIST_BLOCKS"]),
              "hist_psum_bytes_per_round":
                  hist_psum_bytes_per_round(depth, F, n_bins),
              "parity": {}, "rounds_per_sec_per_chip": {}}
    failures = []

    def timed_fit(model, *args, **kwargs):
        t0 = time.perf_counter()
        model.fit(*args, **kwargs)
        return time.perf_counter() - t0

    # 1-chip oracle vs N-chip data-parallel fit (same rows, same cuts)
    m1 = HistGBT(mesh=Mesh(devs[:1], ("data",)), **kw)
    t1 = timed_fit(m1, X, y, cuts=cuts)
    mN = HistGBT(mesh=Mesh(devs[:N_DEV], ("data",)), **kw)
    tN = timed_fit(mN, X, y, cuts=cuts)
    oracle_ok = _save_bytes(m1) == _save_bytes(mN)
    report["parity"]["ensemble_bytes_equal_1_vs_n"] = oracle_ok
    report["rounds_per_sec_per_chip"]["1"] = round(rounds / t1, 3)
    report["rounds_per_sec_per_chip"][str(N_DEV)] = round(
        rounds / tN / N_DEV, 3)
    # CPU virtual devices share host cores, so this "efficiency" is an
    # engine-overhead floor, not a hardware claim (the TPU number comes
    # from bench.py chips=N's scaling block)
    report["scaling_efficiency_cpu"] = round(
        (rounds / tN / N_DEV) / (rounds / t1), 4)
    if not oracle_ok:
        failures.append("1-chip oracle ensemble bytes differ")

    # sharded ingest vs global-put staging, same mesh
    mG = HistGBT(mesh=Mesh(devs[:N_DEV], ("data",)), **kw)
    mG._sharded_ingest_ok = lambda: False   # the global-put fallback
    ddG = mG.make_device_data(X, y, cuts=cuts)
    mS = HistGBT(mesh=Mesh(devs[:N_DEV], ("data",)), **kw)
    ddS = mS.make_device_data(X, y, cuts=cuts)
    bins_ok = np.array_equal(np.asarray(ddG["bins_t"]),
                             np.asarray(ddS["bins_t"]))
    mG.fit_device(ddG)
    mS.fit_device(ddS)
    ingest_ok = bins_ok and _trees_equal(mG, mS)
    report["parity"]["sharded_ingest_bit_identical"] = ingest_ok
    if not ingest_ok:
        failures.append("sharded ingest diverged from global staging")

    # out-of-core: tiny streamed slabs through make_device_data_iter
    os.environ["DMLC_INGEST_CHUNK_ROWS"] = "1024"
    try:
        def slabs():
            for lo in range(0, n, 1024):
                yield X[lo:lo + 1024], y[lo:lo + 1024], None

        mO = HistGBT(mesh=Mesh(devs[:N_DEV], ("data",)), **kw)
        ddO = mO.make_device_data_iter(slabs, n_features=F,
                                       cuts=cuts, n_rows=n)
        mO.fit_device(ddO)
        ooc_ok = (np.array_equal(np.asarray(ddO["bins_t"]),
                                 np.asarray(ddS["bins_t"]))
                  and _save_bytes(mO) == _save_bytes(mS))
    finally:
        del os.environ["DMLC_INGEST_CHUNK_ROWS"]
    report["parity"]["out_of_core_bit_identical"] = ooc_ok
    if not ooc_ok:
        failures.append("out-of-core streamed ingest diverged")

    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    if failures:
        for msg in failures:
            print(f"FAIL: {msg}", file=sys.stderr)
        return 1
    print(f"multichip parity OK: report archived at {out_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
