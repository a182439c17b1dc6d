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

The same listeners keep the compile LEDGER: jax reports, on the
compiling thread and with the program's name, how long it traced,
lowered and compiled (or read back) each program, and every program
becomes one entry of the ``programs`` of the operation open on that
thread (:func:`dmlc_core_tpu.utils.profiler.fold_program`;
``doc/observability.md`` says which event feeds which field).  What
compiles outside any operation is summed in ``stats()["unowned"]``.

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
from dmlc_core_tpu.utils.profiler import current_op, fold_program, span

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
#: the ledger's process tallies: ``unowned`` = entries (and their
#: seconds) that closed with no operation open on their thread;
#: ``nested_traces`` = traces jax reported inside another phase of the
#: same thread, whose seconds that phase already holds
_tallies = {"unowned": {"n": 0, "seconds": 0.0},
            "nested_traces": {"n": 0, "seconds": 0.0}}
#: jax's event -> the field of a ledger entry it feeds
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
#: traces a thread may keep unclaimed (a trace of that many sibling
#: calls): past it the oldest half are folded as they are
_LOOSE_TRACES = 16_384


class _ThreadLedger(threading.local):
    """What this thread's compiles have reported and no entry holds yet."""

    def __init__(self) -> None:
        #: ``(name, start, seconds)`` of the traces no lowering has
        #: claimed and no later phase has enclosed, in order of closing
        self.loose: list = []
        #: the entry (and whether a record took it) of the program this
        #: thread lowered last, until its backend phase completes it
        self.lowered: Optional[Tuple[Dict[str, Any], bool]] = None
        #: all this thread ever reported (``_thread_mark`` /
        #: ``_thread_since``), and as its last backend phase left it
        self.totals = {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
                       "read_s": 0.0, "hit": 0, "miss": 0}
        self.closed = dict(self.totals)


_ledger = _ThreadLedger()

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
    _ledger.totals[name] += 1
    if _metrics.enabled():
        compile_cache_metrics()["events"].inc(1, event=name)


def _on_duration(event: str, duration_secs: float, **kw: Any) -> None:
    if event == _CACHE_READ:
        _ledger.totals["read_s"] += duration_secs
        return
    if event != "/jax/compilation_cache/compile_time_saved_sec":
        return
    with _lock:
        _counts["saved_seconds"] += max(duration_secs, 0.0)
    if _metrics.enabled():
        compile_cache_metrics()["saved"].inc(max(duration_secs, 0.0))


def _fold(program: str, verdict: str = "none",
          opened: Optional[Tuple[Dict[str, Any], bool]] = None,
          **seconds: float) -> Tuple[Dict[str, Any], bool]:
    """One entry, or the phases that complete ``opened``, into the record
    of the operation open on this thread, else into ``unowned``."""
    entry = fold_program(program, verdict,
                         opened[0] if opened and opened[1] else None,
                         **seconds)
    if entry is not None:
        return entry, True
    # a program lowered and then compiled outside any operation is one
    completes_unowned = opened is not None and not opened[1]
    with _lock:
        _tallies["unowned"]["n"] += 0 if completes_unowned else 1
        _tallies["unowned"]["seconds"] += sum(
            v for k, v in seconds.items() if k != "read_s")
    return {"program": program}, False


def _on_time_span(event: str, start_time: float, end_time: float,
                  fun_name: str = "", **kw: Any) -> None:
    """A phase of a compile has ended on this thread.  An entry opens at
    the program's lowering, with the trace that went before it, and is
    complete at its backend phase; a phase with no other around it (a
    compile of an earlier lowering, a trace nothing lowers) is an entry
    of what it has.  Seconds are counted once: the spans of one thread
    nest or follow each other, so the traces that began after this span
    did are the tail of ``loose``, and lie inside it."""
    field = _PHASES.get(event)
    if field is None:
        return
    tl = _ledger
    seconds = max(end_time - start_time, 0.0)
    loose = tl.loose
    nested, inside = 0, 0.0
    while loose and loose[-1][1] >= start_time:
        nested += 1
        inside += loose.pop()[2]
    if nested:
        tl.totals["trace_s"] -= inside
        with _lock:
            _tallies["nested_traces"]["n"] += nested
            _tallies["nested_traces"]["seconds"] += inside
    tl.totals[field] += seconds
    if field == "trace_s":
        loose.append((fun_name, start_time, seconds))
        if len(loose) > _LOOSE_TRACES:
            _fold_loose(loose, len(loose) // 2)
        return
    if field == "lower_s":
        traced = 0.0
        if loose and "<unknown>" in fun_name:
            # a lowering jax has no name for takes its trace's
            fun_name = fun_name.replace("<unknown>", loose[-1][0])
        if loose and fun_name in (loose[-1][0], f"jit({loose[-1][0]})",
                                  f"pmap({loose[-1][0]})"):
            traced = loose.pop()[2]
        _fold_loose(loose, len(loose))
        tl.lowered = _fold(fun_name, trace_s=traced, lower_s=seconds)
        return
    _fold_loose(loose, len(loose))
    opened, tl.lowered = tl.lowered, None
    if opened and "<unknown>" in fun_name:
        fun_name = opened[0]["program"]
    if opened and opened[0]["program"] not in (fun_name, "(more)"):
        opened = None
    # the cache's events fire inside the backend phase they belong to
    since = _thread_since(tl.closed)
    tl.closed = dict(tl.totals)
    _fold(fun_name, since["cache"], opened, backend_s=seconds,
          read_s=since["read_s"])


def _fold_loose(loose: list, n: int) -> None:
    """The oldest ``n`` unclaimed traces become entries of their own."""
    for name, _start, seconds in loose[:n]:
        _fold(name, trace_s=seconds)
    del loose[:n]


def _thread_mark() -> Dict[str, float]:
    """What this thread's compiles have reported so far; pair with
    :func:`_thread_since`."""
    return dict(_ledger.totals)


def _thread_since(mark: Dict[str, float]) -> Dict[str, Any]:
    """This thread's own compile seconds and cache verdict since
    ``mark``, as a ``dmlc.compile`` span carries them."""
    own = {k: v - mark[k] for k, v in _ledger.totals.items()}
    hit, miss = own.pop("hit"), own.pop("miss")
    return {**own, "cache": "miss" if miss else "hit" if hit else "none"}


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
    monitoring.register_event_time_span_listener(_on_time_span)


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
    """Process-local cache evidence: enabled state, directory,
    hit/miss/saved-seconds counts since process start, and the compile
    ledger's tallies — ``unowned = {n, seconds}``, the programs (and
    their trace + lower + backend seconds) no operation's record holds,
    and ``nested_traces = {n, seconds}``, the traces counted inside the
    phase that enclosed them: the records' seconds plus ``unowned`` plus
    ``nested_traces`` are every second jax reported."""
    with _lock:
        counts = dict(_counts)
        tallies = {k: dict(v) for k, v in _tallies.items()}
    return {"enabled": enabled(), "dir": cache_dir(), **counts, **tallies}


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
        self._verdicts: Dict[str, str] = {}
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
            mark = _thread_mark()
            with span("dmlc.compile", op=self._op, what=self._what,
                      program=name) as sp:
                try:
                    self._results[name] = thunk()
                finally:
                    # this thread's own phases and cache traffic: the
                    # programs compiling beside it have theirs
                    own = _thread_since(mark)
                    self._verdicts[name] = own.pop("cache")
                    sp.set(cache=self._verdicts[name],
                           **{k: round(v, 6) for k, v in own.items()})
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
            # the worst of the workers' own verdicts
            self.cache_verdict = next(
                (v for v in ("miss", "hit")
                 if v in self._verdicts.values()), None)
        return self._results
