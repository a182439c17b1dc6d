#!/usr/bin/env python
"""Pre-seed the persistent XLA compile cache (scripts/ci.sh stage).

AOT-compiles the flagship boosting-round ladder — the K-rounds-per-
dispatch program (and remainder, when ``rounds % K != 0``) at the bench
config's exact shapes — into the persistent compile cache
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.compile_cache``;
see ``base/compile_cache.py``), WITHOUT materializing any data:
``lower().compile()`` works on ShapeDtypeStructs, so warming the
10M-row program costs compile time only.  A later ``bench.py`` (or any
fit at the same config) against the same directory then deserializes
instead of compiling, and the bench JSON reports ``compile_cache: hit``.

Idempotent and cheap when warm: a second run joins in cache-read time.
Config mirrors bench.py's env (``BENCH_ROWS``/``BENCH_FEATURES``/
``BENCH_ROUNDS``/``BENCH_DEPTH``/``BENCH_BINS``/``BENCH_CHIPS``); the
ladder compiles for the CURRENT backend (run on the TPU host to warm
the TPU cache — a CPU-CI run warms the CPU lanes' shared dir).
``WARM_CACHE_FORCE_CPU=N`` pins N virtual CPU devices first (CI).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("WARM_CACHE_FORCE_CPU"):
    from dmlc_core_tpu.utils import force_cpu_devices
    force_cpu_devices(int(os.environ["WARM_CACHE_FORCE_CPU"]))

import numpy as np  # noqa: E402


def main() -> int:
    rows = int(os.environ.get("BENCH_ROWS", 10_000_000))
    feats = int(os.environ.get("BENCH_FEATURES", 28))
    rounds = int(os.environ.get("BENCH_ROUNDS", 100))
    depth = int(os.environ.get("BENCH_DEPTH", 6))
    n_bins = int(os.environ.get("BENCH_BINS", 256))
    chips = int(os.environ.get("BENCH_CHIPS", "0") or 0)

    from dmlc_core_tpu.base import compile_cache as cc
    from dmlc_core_tpu.models import HistGBT
    from dmlc_core_tpu.models.histgbt import _RoundProgramWarmup
    from dmlc_core_tpu.parallel.mesh import local_mesh

    cc.configure()
    t0 = time.time()
    mesh = local_mesh(chips or None)
    model = HistGBT(n_trees=rounds, max_depth=depth, n_bins=n_bins,
                    learning_rate=0.1, mesh=mesh)
    n_padded = rows + ((-rows) % model._pad_multiple())
    warm = _RoundProgramWarmup(model, feats, n_padded)
    execs = warm.join()
    stats = cc.stats()
    record = {
        "check": "warm_compile_cache",
        "rows": rows, "features": feats, "rounds": rounds,
        "chips": mesh.devices.size,
        "programs": sorted(execs),
        "compile_seconds": round(warm.compile_seconds, 3),
        "wall_seconds": round(time.time() - t0, 3),
        "cache_verdict": warm.cache_verdict or "warm",
        **stats,
    }
    print(json.dumps(record))
    if not execs:
        print("FAIL: no round programs compiled", file=sys.stderr)
        return 1
    if not stats["enabled"]:
        print("FAIL: persistent compile cache is disabled "
              "(DMLC_COMPILE_CACHE=0?)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
