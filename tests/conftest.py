"""Test harness config.

Tests run on CPU with a virtual 8-device mesh so the multi-chip sharding
path (shard_map / psum over a named Mesh) is exercised without TPU hardware
— the TPU-world analogue of the reference's ``dmlc_tracker/local.py``
multi-process testing pattern (SURVEY.md §4).  The device count has to be
asked for BEFORE any jax backend init (dmlc_core_tpu.utils.platform).
"""

import gc
import sys
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dmlc_core_tpu.utils import force_cpu_devices

force_cpu_devices(8)

import jax  # noqa: E402

from dmlc_core_tpu.base import compile_cache  # noqa: E402

# Persistent XLA compilation cache: the suite's wall time is dominated by
# first-compiles of a few dozen distinct programs, so a warm rerun — the
# dev loop — skips nearly all of it.  The directory is the library's own
# choice (JAX_COMPILATION_CACHE_DIR if set, else <repo>/.compile_cache),
# shared with bench/scripts/children; a cold checkout starts empty.
compile_cache.configure()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True, scope="module")
def _release_executables():
    """Drop every compiled executable after each test module, before
    the process runs out of memory mappings.

    XLA:CPU maps three small regions per JIT-compiled kernel object and
    keeps them for as long as the executable lives; jax's jit caches and
    the repo's process-wide program tables pin every executable for the
    life of the process.  One pytest process that runs the whole suite
    crossed the kernel's per-process mapping cap (``vm.max_map_count``,
    65530) about 83% of the way through, and the next ``mmap`` failure
    surfaced as a SIGSEGV in whatever native code asked for memory.
    Recompiles in later modules are persistent-cache reads (releasing
    only above a mapping count was tried: no faster, more code).

    Reproducer (jax/jaxlib 0.9.0, no repo code): pre-fill ~58k one-page
    ``mmap.mmap(-1, 4096)`` with alternating protection, then
    ``jax.jit`` + run distinct small programs in a loop, keeping them;
    near 65.4k lines in ``/proc/self/maps`` XLA logs "LLVM compilation
    error: Cannot allocate memory" and the process dies with SIGSEGV.
    """
    yield
    from dmlc_core_tpu.models import histgbt

    histgbt._ROUND_FN_CACHE.clear()
    histgbt._AOT_EXEC_CACHE.clear()
    jax.clear_caches()
    gc.collect()
