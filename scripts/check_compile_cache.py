#!/usr/bin/env python
"""Compile-cache wiring check (scripts/ci.sh stage).

Runs one tiny in-core GBT fit with the persistent XLA compile cache
placed from outside by ``JAX_COMPILATION_CACHE_DIR`` and prints the
cache evidence as one JSON line.  ``DMLC_COMPILE_CACHE_EXPECT`` asserts the outcome:

* ``miss`` — fresh dir: something must have been compiled AND written;
* ``hit``  — second process against the same dir: at least one program
  must have been served from disk, i.e. the wiring survives jax-version
  drift (cache key scheme, config names, event names).

ci.sh runs this twice against one mktemp dir — cold then warm — so the
cold-start contract (`doc/performance.md`) is guarded by CI, not only
by the in-process unit tests.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dmlc_core_tpu.utils import force_cpu_devices  # noqa: E402

force_cpu_devices(2)

import numpy as np  # noqa: E402


def main() -> int:
    expect = os.environ.get("DMLC_COMPILE_CACHE_EXPECT", "")
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not cache_dir:
        print("JAX_COMPILATION_CACHE_DIR must be set", file=sys.stderr)
        return 2

    from dmlc_core_tpu.base import compile_cache as cc
    from dmlc_core_tpu.models import HistGBT

    rng = np.random.default_rng(0)
    X = rng.normal(size=(128, 4)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    model = HistGBT(n_trees=2, max_depth=2, n_bins=8)
    model.fit(X, y)
    model.predict(X[:8])

    stats = cc.stats()
    entries = (len(os.listdir(cache_dir))
               if os.path.isdir(cache_dir) else 0)
    record = {"check": "compile_cache", "expect": expect,
              "cache_entries": entries, **stats}
    print(json.dumps(record))

    if stats["dir"] != cache_dir:
        print(f"FAIL: cache dir {stats['dir']!r} != requested "
              f"{cache_dir!r}", file=sys.stderr)
        return 1
    if expect == "miss" and not (stats["misses"] > 0 and entries > 0):
        print("FAIL: expected compile-cache misses + written entries "
              "on a cold dir", file=sys.stderr)
        return 1
    if expect == "hit" and not stats["hits"] > 0:
        print("FAIL: expected compile-cache hits on a warm dir "
              "(persistent cache wiring broken?)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
