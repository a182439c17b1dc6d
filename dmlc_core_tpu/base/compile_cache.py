"""Persistent XLA compilation cache wiring + cold-start instrumentation.

The XLA compiles of a cold start (tens of seconds on the north-star
config) are re-paid by every ``bench.py`` run, every elastic-recovery
relaunch, and every serve restart, even though the programs are
byte-identical each time.  JAX ships a persistent compilation cache
(serialized executables keyed on the HLO + device topology) that turns
a repeat compile into a disk read; this module is the ONE place that
wires it, so every engine (in-core / external / sparse GBT, serve
runners, bench, chip_smoke) gets warm-start behavior.

Where the cache lives is decided from OUTSIDE the program:

* ``JAX_COMPILATION_CACHE_DIR`` set — jax has adopted that directory at
  import and nothing here ever points it elsewhere;
* unset — ONE fixed, git-ignored directory in the checkout
  (``<repo>/.compile_cache``), the same for the library, the tests, the
  smoke, the bench and every child process.  The path is part of what
  makes a later process find the entries, so it carries no pid, temp
  name or timestamp.

``DMLC_COMPILE_CACHE=0`` disables the wiring (no jax config is touched
at all).

When enabled, the write thresholds are opened up
(``jax_persistent_cache_min_compile_time_secs=0``, no minimum entry
size): this substrate compiles a few dozen distinct programs at most,
and a sub-second program that a serve restart would otherwise recompile
per bucket is exactly what the cache exists to skip.

Instrumentation: jax's monitoring events for cache hits / misses /
compile-time-saved are forwarded into :mod:`dmlc_core_tpu.base.metrics`
(``dmlc_compile_cache_events_total{event=hit|miss}``,
``dmlc_compile_cache_saved_seconds_total``) and mirrored in process-
local counters that :func:`stats` reports even with metrics disabled —
``bench.py`` stamps its final JSON with ``compile_cache: hit|miss``
from exactly this.

:class:`BackgroundCompiler` is the shared cold-start overlap helper:
it runs AOT ``lower(...).compile()`` thunks concurrently on
:class:`~dmlc_core_tpu.io.thread_group.ThreadGroup` workers so compiles
proceed while ingest (quantile sketch, binning, H2D staging) runs on
the main thread — see ``models/histgbt.py`` for the flagship consumer
and ``doc/performance.md`` for the full cold-start story.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from dmlc_core_tpu.base import metrics as _metrics
from dmlc_core_tpu.base.logging import LOG
from dmlc_core_tpu.base.parameter import get_env
from dmlc_core_tpu.base.timer import get_time
from dmlc_core_tpu.utils.profiler import current_op, span

__all__ = [
    "BackgroundCompiler", "cache_dir", "compile_cache_metrics",
    "configure", "enabled", "set_cache_dir", "stats",
]

#: on-disk location when ``JAX_COMPILATION_CACHE_DIR`` is not set: one
#: fixed directory at the root of the checkout (listed in .gitignore)
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".compile_cache")

_lock = threading.Lock()
#: process-local event counts (kept even when base.metrics is disabled
#: — stats() is evidence for bench records, not optional telemetry)
_counts = {"hits": 0, "misses": 0, "saved_seconds": 0.0}
_listeners_registered = False

_M: Dict[str, Any] = {}


def compile_cache_metrics() -> Dict[str, Any]:
    """Lazily declared instrument handles in the default registry."""
    if not _M:
        r = _metrics.default_registry()
        _M.update({
            "events": r.counter(
                "compile_cache_events_total",
                "persistent XLA compile cache events (hit = executable "
                "deserialized from disk, miss = compiled then written)",
                labels=("event",)),
            "saved": r.counter(
                "compile_cache_saved_seconds_total",
                "compile seconds skipped via persistent-cache hits "
                "(original compile time minus retrieval time)"),
            "compile": r.histogram(
                "compile_seconds",
                "wall seconds per AOT program compile (cache hits "
                "included — they appear as near-zero observations)",
                labels=("what",)),
        })
    return _M


def _on_event(event: str, **kw: Any) -> None:
    name = {"/jax/compilation_cache/cache_hits": "hit",
            "/jax/compilation_cache/cache_misses": "miss"}.get(event)
    if name is None:
        return
    with _lock:
        _counts[name + ("s" if name == "hit" else "es")] += 1
    if _metrics.enabled():
        compile_cache_metrics()["events"].inc(1, event=name)


def _on_duration(event: str, duration_secs: float, **kw: Any) -> None:
    if event != "/jax/compilation_cache/compile_time_saved_sec":
        return
    with _lock:
        _counts["saved_seconds"] += max(duration_secs, 0.0)
    if _metrics.enabled():
        compile_cache_metrics()["saved"].inc(max(duration_secs, 0.0))


def _register_listeners() -> None:
    """Forward jax's cache monitoring events — once per process.  The
    listeners only count, so they are registered unconditionally: the
    test harness enables the jax cache on its own and the counters must
    reflect that reality too."""
    global _listeners_registered
    with _lock:
        if _listeners_registered:
            return
        _listeners_registered = True
    from jax._src import monitoring
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


_register_listeners()


def enabled() -> bool:
    """``DMLC_COMPILE_CACHE`` (default on)."""
    return get_env("DMLC_COMPILE_CACHE", True, bool)


def cache_dir() -> Optional[str]:
    """The jax cache directory currently in effect (None = no cache)."""
    return jax.config.jax_compilation_cache_dir


def configure() -> bool:
    """Idempotently wire jax's persistent compilation cache.

    Safe to call before every compile site (each engine does).  Returns
    True when the cache is active.  ``DMLC_COMPILE_CACHE=0`` is a
    strict no-op: nothing in jax.config is touched.  A directory jax
    already holds — from ``JAX_COMPILATION_CACHE_DIR``, or the
    :func:`set_cache_dir` test hook — is never replaced; only when
    there is none does the fixed in-checkout directory go in.
    """
    if not enabled():
        return False
    if jax.config.jax_compilation_cache_dir is None:
        set_cache_dir(_DEFAULT_DIR)
    else:
        _set_cache_options()
    return True


def set_cache_dir(path: str) -> None:
    """Point the persistent cache at ``path`` (created lazily by jax) —
    :func:`configure`'s own setter and the hook tests use to isolate a
    cache.

    Also resets jax's sticky cache handle so a redirect AFTER a compile
    has happened takes effect — without the reset the first-initialized
    directory would silently keep winning (test isolation needs this).
    """
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_compilation_cache_dir", path)
    _set_cache_options()
    cc.reset_cache()
    LOG("DEBUG", "compile_cache: persistent XLA cache at %s", path)


def _set_cache_options() -> None:
    """Cache EVERY program: the default 1 s compile-time floor would
    skip most CPU-backend programs and every small serve bucket — the
    exact compiles a warm restart must not re-pay.

    And key every program on its metadata too.  The ``jax.named_scope``
    marks of the device phases (``dmlc.bin``, ``dmlc.round.L3.hist`` —
    doc/observability.md) live in the HLO's ``op_name`` metadata, which
    jax strips before hashing a program: a program that differs from a
    cached one only in its scopes would be "hit" and come back WITHOUT
    them, and the device trace would lose the phase.  With the metadata
    in the key such a program compiles once more instead.  Locations
    are cut to their innermost frame, so the key follows where in the
    library an operation is written and under which scope, not who
    called the library (``jax_include_full_tracebacks_in_locations=
    False`` would do the same and drop the scopes from ``op_name``)."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)


def stats() -> Dict[str, Any]:
    """Process-local cache evidence: enabled state, directory, and
    hit/miss/saved-seconds counts since process start."""
    with _lock:
        counts = dict(_counts)
    return {"enabled": enabled(), "dir": cache_dir(), **counts}


def marker() -> Tuple[int, int]:
    """(hits, misses) snapshot; pair with :func:`verdict`."""
    with _lock:
        return _counts["hits"], _counts["misses"]


def verdict(mark: Tuple[int, int]) -> Optional[str]:
    """Classify cache activity since ``mark``: ``"hit"`` (served at
    least partly from disk, nothing newly compiled), ``"miss"``
    (something compiled + written), or None (no cache traffic — cache
    off, or every program came from jax's in-memory caches)."""
    hits, misses = marker()
    dh, dm = hits - mark[0], misses - mark[1]
    if dm > 0:
        return "miss"
    if dh > 0:
        return "hit"
    return None


class BackgroundCompiler:
    """Run named compile thunks concurrently on daemon workers.

    The cold-start overlap primitive (see module docstring): each thunk
    typically does ``jit(fn).lower(*avals).compile()`` and returns the
    compiled executable; workers run while the caller's main thread
    does ingest work, and :meth:`join` blocks only for whatever compile
    time the ingest did not already cover.

    A thunk that raises — the compiler refusing a program — is
    re-raised by :meth:`join` on the caller's thread: a program that
    does not compile must stop the run with the compiler's message, not
    be retried down another path.  ``compile_seconds`` after join is the
    longest single worker wall (the critical path; workers run
    concurrently), ``join_wait_seconds`` the non-overlapped residue the
    caller paid.
    """

    def __init__(self, jobs: Dict[str, Callable[[], Any]],
                 what: str = "warmup") -> None:
        from dmlc_core_tpu.io.thread_group import ThreadGroup

        configure()
        self._what = what
        self._results: Dict[str, Any] = {}
        self._walls: Dict[str, float] = {}
        self._mark = marker()
        self._joined = False
        self.compile_seconds = 0.0
        self.join_wait_seconds = 0.0
        self.cache_verdict: Optional[str] = None
        # the workers' spans join the operation that starts them
        self._op = current_op()
        self._grp = ThreadGroup()
        for name, thunk in jobs.items():
            self._grp.create(f"compile-{name}",
                             self._runner(name, thunk))

    def _runner(self, name: str, thunk: Callable[[], Any]):
        def run(_shutdown) -> None:
            t0 = get_time()
            mark = marker()
            with span("dmlc.compile", op=self._op, what=self._what,
                      program=name) as sp:
                try:
                    self._results[name] = thunk()
                finally:
                    # process-wide counts: two programs compiling at
                    # once see each other's cache traffic
                    sp.set(cache=verdict(mark) or "none")
                    self._walls[name] = get_time() - t0
                    if _metrics.enabled():
                        compile_cache_metrics()["compile"].observe(
                            self._walls[name],
                            what=f"{self._what}:{name}")
        return run

    def join(self) -> Dict[str, Any]:
        """Wait for every worker; returns name → compiled result, or
        raises the first thunk's exception (see class docstring)."""
        t0 = get_time()
        self._grp.join_all()         # re-raises what a thunk raised
        if not self._joined:
            self._joined = True
            self.join_wait_seconds = get_time() - t0
            self.compile_seconds = max(self._walls.values(), default=0.0)
            self.cache_verdict = verdict(self._mark)
        return self._results
