"""Tracing & profiling — a strict superset of the reference's timing story.

The reference's only primitive is ``include/dmlc/timer.h :: GetTime()``
(SURVEY.md §5: "tracing/profiling: essentially none").  The TPU substrate
owes more: step time vs infeed stall is THE number that decides whether
the host pipeline (ThreadedIter → device_put) keeps the chip busy.  This
module provides

* :func:`device_trace` — context manager around ``jax.profiler.trace``:
  captures an XLA/TensorBoard profile (HLO timelines, TPU utilization)
  into a logdir;
* :class:`span` — THE way to mark a host phase of the program: one call
  site, three sinks.  It always enters a ``jax.profiler.TraceAnnotation``
  (so the phase sits in the profiler's own trace, on the device trace's
  clock, next to the XLA ops — free when no trace is being taken);
  while the metrics layer is on (the default) it folds its wall into
  the record of its operation (:func:`op_log`: one bounded record an
  operation, there when no trace is — set-up, every untraced run); and,
  only while host tracing is on, it records the same name and counts
  into :func:`global_tracer`.  Device phases are marked where they are
  traced, with ``jax.named_scope`` (see ``doc/observability.md``);
* :class:`Tracer` — a dependency-free host-side event tracer writing
  Chrome ``chrome://tracing`` / Perfetto JSON, so host pipeline phases
  (read, parse, device_put, step) can be eyeballed against each other
  without TensorBoard.

All host events go through ``base.timer.get_time`` so Tracer timestamps
line up with the rest of the framework's timing.

Hot-path integration (PR: observability substrate): the instrumented
pipelines (ThreadedIter, parsers, collectives, the GBT engines) emit
scopes/instants to :func:`global_tracer` ONLY while host tracing is
switched on (:func:`set_tracing` / ``DMLC_TRACE=1``) — tracing is
event-per-item and unbounded-ish in volume, so unlike the aggregate
metrics layer (``base.metrics``) it defaults OFF.  The Tracer buffer is
capped (``max_events``) so a scope left enabled for a long run degrades
to dropped events, never to unbounded host memory.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import threading
import time
from typing import Any, Deque, Dict, Iterator, List, Optional

from dmlc_core_tpu.base import metrics as _metrics
from dmlc_core_tpu.base.timer import get_time

__all__ = ["device_trace", "span", "phase", "count_in_op", "current_op",
           "fold_program", "op_log", "op_log_dropped", "Tracer",
           "global_tracer", "tracing_enabled", "set_tracing"]

_TRACING = os.environ.get("DMLC_TRACE", "0").lower() in ("1", "true", "on",
                                                         "yes")


def tracing_enabled() -> bool:
    """Fast global switch read by hot-path call sites before they touch
    :func:`global_tracer` — one global read + branch when off."""
    return _TRACING


def set_tracing(on: bool) -> None:
    """Enable/disable host-event tracing process-wide (also:
    ``DMLC_TRACE=1``)."""
    global _TRACING
    _TRACING = bool(on)


@contextlib.contextmanager
def device_trace(logdir: str) -> Iterator[None]:
    """Capture a JAX/XLA device profile into ``logdir``.

    View with TensorBoard's profile plugin.  Degrades to a no-op if the
    profiler cannot start (e.g. another trace is active).
    """
    import jax

    try:
        os.makedirs(logdir, exist_ok=True)
        jax.profiler.start_trace(logdir)
        started = True
    except Exception:  # noqa: BLE001 — profiling must never break training
        started = False
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001
                pass


#: one process-wide counter names operations: every top-level span takes
#: the next value as ``op`` and its children inherit it
_op_ids = itertools.count(1)
#: per-thread stack of the spans open on that thread
_open = threading.local()


def current_op() -> Optional[int]:
    """``op`` of the innermost :class:`span` open on this thread (None
    outside any) — what a caller hands to a worker thread so the spans
    the worker opens join the same operation."""
    stack = getattr(_open, "spans", None)
    return stack[-1].counts["op"] if stack else None


def count_in_op(**counts: float) -> None:
    """Add numbers to the counts of the operation open on this thread
    (its top-level :class:`span`; nothing outside any): how a layer
    below an operation — a page reader, a densifier — reports what it
    moved without knowing who consumes it.  The sums are in the
    operation's :func:`op_log` record; the opener hands them to the
    trace with :meth:`span.set` before it closes."""
    stack = getattr(_open, "spans", None)
    if stack:
        top = stack[0].counts
        for key, value in counts.items():
            top[key] = top.get(key, 0) + value


def phase(name: str, **counts: Any) -> Any:
    """:class:`span` ``name`` where an operation is open on this thread,
    else a context that marks nothing: for code BELOW the entry points
    (``DiskRowIter.next_block``, ``Dataset.dense_slabs``), whose wait or
    copy is a phase of whichever operation pulls it, and which outside
    any would draw an ``op`` and a record of its own per page."""
    if getattr(_open, "spans", None):
        return span(name, **counts)
    return contextlib.nullcontext()


#: records the ring keeps; the oldest go first.  A 20 s window of the
#: scoring benchmark is ~6,200 ``predict`` calls, one record each
OP_LOG_RECORDS = 16_384
_log: Deque[Dict[str, Any]] = collections.deque(maxlen=OP_LOG_RECORDS)
#: op -> the record of an operation whose top-level span is still open
_open_records: Dict[int, Dict[str, Any]] = {}
#: orders a joined span's fold against its operation's close, and the
#: ring's writers against its readers
_log_lock = threading.Lock()
_log_appended = 0
#: a record's ``compile`` before any span with a ``cache`` verdict joined
_NO_VERDICTS = {"hit": 0, "miss": 0, "seconds": 0.0}
#: entries a record's ``programs`` keeps; what comes after them is summed
#: into one more, named ``"(more)"``
OP_LOG_PROGRAMS = 64
#: the seconds of a ``programs`` entry (``read_s`` lies inside ``backend_s``)
PROGRAM_SECONDS = ("trace_s", "lower_s", "backend_s", "read_s")
_VERDICT_RANK = {"none": 0, "hit": 1, "miss": 2}


def _new_record(op: int, name: str, start: float) -> Dict[str, Any]:
    return {"op": op, "name": name, "start": start, "end": start,
            "counts": None, "children": {}, "compile": None}


def _count_verdict(rec: Dict[str, Any], cache: str, seconds: float) -> None:
    """One more span with a ``cache`` verdict under ``rec``."""
    verdicts = rec["compile"]
    if verdicts is None:
        rec["compile"] = verdicts = dict(_NO_VERDICTS)
    verdicts[cache] = verdicts.get(cache, 0) + 1
    verdicts["seconds"] += seconds


def _fold(children: Dict[str, List[Any]], name: str, n: int,
          seconds: float, longest: float, nbytes: int) -> None:
    """Add ``n`` spans named ``name`` to a record's ``children``."""
    child = children.get(name)
    if child is None:
        children[name] = [n, seconds, longest, nbytes]
        return
    child[0] += n
    child[1] += seconds
    if longest > child[2]:
        child[2] = longest
    child[3] += nbytes


def fold_program(program: str, verdict: str, opened: Optional[Dict[str, Any]]
                 = None, **seconds: float) -> Optional[Dict[str, Any]]:
    """One program's compile phases into the record of the operation
    open on this thread: ``base/compile_cache.py``'s listeners call this
    when jax has traced, lowered, compiled or read back a program here,
    and no span does.  ``seconds`` are some of :data:`PROGRAM_SECONDS`.
    The entry is ``thread`` ``"own"`` on the thread that opened the
    operation and ``"joined"`` on one that joined it by ``op=``, ``under``
    the innermost ``dmlc.*`` span open here; where the joined operation
    has closed, the entry waits for the record the joining span closes
    as (:meth:`span._join`).  ``opened`` is the entry this thread got
    back when the same program was lowered: while it still lies in the
    record open here, the new phases complete it in place.  Past
    :data:`OP_LOG_PROGRAMS` entries a record sums the rest into one
    named ``"(more)"`` with their count ``n``, its ``thread`` and
    ``under`` ``"mixed"`` unless all agree.  Returns the entry, or None
    where no operation is open here (or the record is off): the caller
    keeps those seconds."""
    stack = getattr(_open, "spans", None)
    if not stack:
        return None
    top = stack[0]
    where = {"thread": "own", "under": next(
        (s.name for s in reversed(stack) if s.name.startswith("dmlc.")),
        stack[-1].name)}
    rec = top._rec
    with _log_lock:
        if rec is not None:
            programs = rec.setdefault("programs", [])
        elif top._top or not _metrics.enabled():
            return None              # opened while the record was off
        else:
            where["thread"] = "joined"
            rec = _open_records.get(top.counts["op"])
            programs = (rec.setdefault("programs", []) if rec is not None
                        else _open.__dict__.setdefault("programs", []))
        completes = (opened is not None
                     and any(p is opened for p in programs))
        if completes and opened["program"] != "(more)":
            for key, value in seconds.items():
                opened[key] += value
            opened.update(where, verdict=verdict)
            return opened
        if not completes and len(programs) <= OP_LOG_PROGRAMS:
            full = len(programs) == OP_LOG_PROGRAMS
            programs.append({
                "program": "(more)" if full else program,
                **dict.fromkeys(PROGRAM_SECONDS, 0.0), **seconds,
                "verdict": verdict, **where, **({"n": 1} if full else {})})
            return programs[-1]
        more = programs[-1]
        more["n"] += not completes
        for key, value in seconds.items():
            more[key] += value
        if _VERDICT_RANK[verdict] > _VERDICT_RANK[more["verdict"]]:
            more["verdict"] = verdict
        for key, value in where.items():
            if more[key] != value:
                more[key] = "mixed"
        return more


def op_log() -> List[Dict[str, Any]]:
    """The per-operation record of the spans, oldest first: one plain
    dict for every top-level :class:`span` that has closed — ``op``,
    ``name``, ``start`` and ``end`` (``base.timer.get_time``'s clock:
    the host's, not the profiler's), its ``counts``, per child span
    name ``children[name] = [n, seconds, max_seconds, bytes]`` (every
    span below it on any thread that carried its ``op``; ``bytes`` sums
    that count where a span has one) and ``compile = {hit, miss,
    seconds}`` (the spans of other threads that joined it with a
    ``cache`` verdict) and ``programs``: one entry, in the order they
    were folded, for every program jax traced, lowered, compiled or read
    back from the persistent cache on a thread that had the operation
    open (:func:`fold_program`).  A span that joined by ``op=`` and
    closed after its operation's top-level span is a record of its own
    under that ``op``.  Kept whenever ``base.metrics.enabled()``, trace or no
    trace, in a ring of :data:`OP_LOG_RECORDS` (see
    :func:`op_log_dropped`); a trace's spans are joined to it by
    ``op``, not by time."""
    with _log_lock:
        records = list(_log)
    # a closed record is written no more: copied outside the lock
    return [{**r,
             "counts": {k: v for k, v in r["counts"].items() if k != "op"},
             "children": {k: list(v) for k, v in r["children"].items()},
             "compile": dict(r["compile"] or _NO_VERDICTS),
             "programs": [dict(p) for p in r.get("programs", ())]}
            for r in records]


def op_log_dropped() -> int:
    """Records the ring has overwritten so far (the oldest go first)."""
    with _log_lock:
        return _log_appended - len(_log)


class span:
    """Mark one host phase: ``with span("dmlc.ingest.pad", rows=n): ...``.

    The name, start and end go to the profiler's trace as a
    ``TraceAnnotation`` whose stats are ``counts`` plus ``op``: the
    identifier all spans of one operation share.  A span opened inside
    another on the same thread is its child (nesting is the parent
    link) and inherits its ``op``; a top-level span draws a fresh one;
    a span on another thread joins an operation by passing
    ``op=`` explicitly (:func:`current_op` read on the caller's thread).
    While ``base.metrics.enabled()`` a top-level span is a record of
    :func:`op_log` and every other span is folded into the record of its
    ``op``.  While :func:`tracing_enabled`, the same name and counts are
    also recorded as a complete event in :func:`global_tracer`.

    Nothing is made synchronous: a span around an enqueue measures the
    enqueue.  :meth:`set` adds counts known only once the work is done;
    ``seconds`` is the span's wall once it has closed.
    """

    __slots__ = ("name", "counts", "seconds", "_ann", "_start_us", "_t0",
                 "_rec", "_top")

    def __init__(self, name: str, **counts: Any) -> None:
        self.name = name
        self.counts = counts

    def __enter__(self) -> "span":
        import jax

        stack = _open.__dict__.setdefault("spans", [])
        # the record this span folds into without a lock: its own, or
        # the one its parent on this thread folds into.  A span that
        # joins by ``op=`` has none, and finds its operation's at exit
        self._rec = None
        self._top = False
        if self.counts.get("op") is None:
            if stack:
                self.counts["op"] = stack[-1].counts["op"]
                self._rec = stack[-1]._rec
            else:
                self.counts["op"] = next(_op_ids)
                self._top = True
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.counts)
        self._ann.__enter__()
        stack.append(self)
        self._start_us = global_tracer()._us() if _TRACING else None
        self._t0 = get_time()
        if self._top and _metrics.enabled():
            self._rec = _open_records[self.counts["op"]] = _new_record(
                self.counts["op"], self.name, self._t0)
        return self

    def set(self, **counts: Any) -> None:
        """Add counts to the open span (a cache verdict, rows seen)."""
        self.counts.update(counts)
        self._ann.set_metadata(**counts)

    def __exit__(self, *exc: Any) -> None:
        end = get_time()
        self.seconds = end - self._t0
        self._ann.__exit__(*exc)
        _open.spans.pop()
        if self._start_us is not None:
            global_tracer()._complete(self.name, self._start_us,
                                      self.counts)
        rec = self._rec
        if rec is None:
            if _metrics.enabled():
                self._join(end)
        elif self._top:
            self._close(rec, end)
        else:
            # this thread alone writes ``children``: no lock.  ``_fold``
            # written out: a call is a sixth of what a span may cost
            seconds = self.seconds
            child = rec["children"].get(self.name)
            if child is None:
                rec["children"][self.name] = [
                    1, seconds, seconds, self.counts.get("bytes", 0)]
                return
            child[0] += 1
            child[1] += seconds
            if seconds > child[2]:
                child[2] = seconds
            child[3] += self.counts.get("bytes", 0)

    def _close(self, rec: Dict[str, Any], end: float) -> None:
        """Finish a record and hand it to the ring.  The record keeps the
        span's own ``counts`` (nothing else holds them once it has
        closed; :func:`op_log` leaves ``op`` out)."""
        global _log_appended
        rec["end"] = end
        rec["counts"] = self.counts
        with _log_lock:
            _open_records.pop(rec["op"], None)
            if "joined" in rec:
                for name, child in rec.pop("joined").items():
                    _fold(rec["children"], name, *child)
            _log.append(rec)
            _log_appended += 1

    def _join(self, end: float) -> None:
        """A span with no record on its own thread (it joined by
        ``op=``, or metrics came on while it was open): fold it into the
        open record of its ``op`` — under ``joined``, which the close
        merges, so that the operation's own thread never takes the lock
        — or, where that operation has closed (a compile worker outlives
        the ingest that started it), keep it as a record of its own."""
        cache = self.counts.get("cache")
        with _log_lock:
            rec = _open_records.get(self.counts["op"])
            if rec is not None:
                _fold(rec.setdefault("joined", {}), self.name, 1,
                      self.seconds, self.seconds,
                      self.counts.get("bytes", 0))
                if cache is not None:
                    _count_verdict(rec, cache, self.seconds)
                return
        rec = _new_record(self.counts["op"], self.name, self._t0)
        if cache is not None:
            _count_verdict(rec, cache, self.seconds)
        # the programs this thread compiled once its operation had closed
        # (``fold_program``): only an outliving worker comes this way
        waiting = _open.__dict__.pop("programs", None)
        if waiting:
            rec["programs"] = waiting
        self._close(rec, end)


class Tracer:
    """Host-side event tracer → Chrome/Perfetto trace JSON.

    >>> tr = Tracer()
    >>> with tr.scope("parse"):
    ...     ...
    >>> tr.counter("queue_depth", 3)
    >>> tr.save("/tmp/trace.json")   # open in chrome://tracing / Perfetto

    Thread-safe; events carry real thread ids so producer/consumer
    overlap (the ThreadedIter pipeline) is visible on separate rows.
    The buffer is bounded: past ``max_events`` new events are dropped
    (and counted — ``dropped`` rides into the saved trace's metadata)
    rather than growing host memory without limit.
    """

    def __init__(self, max_events: int = 200_000) -> None:
        self._events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        # _t0 (monotonic) timestamps events; _wall0 is the SAME instant
        # on the wall clock, so cross-process merges (trace_collect) can
        # line shards up on a shared epoch despite per-process _t0s
        self._t0 = get_time()
        self._wall0 = time.time()
        self._max_events = max_events
        self.dropped = 0
        #: process identity stamped into saved traces (set_meta)
        self.role = ""
        self.rank = -1

    def set_meta(self, role: Optional[str] = None,
                 rank: Optional[int] = None) -> None:
        """Stamp this process's fleet identity (role/rank) into every
        subsequent :meth:`save` — the Perfetto ``process_name`` row and
        the merge metadata ``trace_collect`` keys shards by."""
        with self._lock:
            if role is not None:
                self.role = str(role)
            if rank is not None:
                self.rank = int(rank)

    def _us(self) -> float:
        return (get_time() - self._t0) * 1e6

    def _append(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) >= self._max_events:
                self.dropped += 1
                return
            self._events.append(ev)

    @contextlib.contextmanager
    def scope(self, name: str, **args: Any) -> Iterator[None]:
        """A complete ("X") duration event on the calling thread's row."""
        start = self._us()
        try:
            yield
        finally:
            self._complete(name, start, args)

    def _complete(self, name: str, start_us: float,
                  args: Dict[str, Any]) -> None:
        """Append the "X" event of a region that began at ``start_us``
        and ends now."""
        self._append({
            "name": name, "ph": "X", "ts": start_us,
            "dur": self._us() - start_us, "pid": os.getpid(),
            "tid": threading.get_ident(),
            "args": dict(args),
        })

    def instant(self, name: str, **args: Any) -> None:
        self._append({
            "name": name, "ph": "i", "ts": self._us(), "s": "t",
            "pid": os.getpid(), "tid": threading.get_ident(),
            "args": args or {},
        })

    def counter(self, name: str, value: float, series: str = "value") -> None:
        self._append({
            "name": name, "ph": "C", "ts": self._us(),
            "pid": os.getpid(), "args": {series: value},
        })

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    @staticmethod
    def _metadata_events(events: List[Dict[str, Any]], role: str,
                         rank: int) -> List[Dict[str, Any]]:
        """Chrome-trace "M" metadata rows: without them, two processes'
        traces opened together in Perfetto are indistinguishable."""
        pid = os.getpid()
        pname = (f"{role}-{rank}" if role else "process")
        meta: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": f"{pname} pid={pid}"},
        }]
        tids = {ev["tid"] for ev in events if "tid" in ev}
        names = {t.ident: t.name for t in threading.enumerate()}
        for tid in sorted(tids):
            meta.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": names.get(tid, f"thread-{tid}")},
            })
        return meta

    def save(self, path: str) -> str:
        with self._lock:
            events = list(self._events)
            payload: Dict[str, Any] = {
                "traceEvents": self._metadata_events(
                    events, self.role, self.rank) + events,
                "displayTimeUnit": "ms",
                "otherData": {
                    "dropped_events": self.dropped,
                    "epoch_us": self._wall0 * 1e6,
                    "pid": os.getpid(),
                    "role": self.role,
                    "rank": self.rank,
                },
            }
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(payload, f)
        return path


_global: Optional[Tracer] = None
_global_lock = threading.Lock()


def global_tracer() -> Tracer:
    """Process-wide Tracer (created on first use)."""
    global _global
    with _global_lock:
        if _global is None:
            _global = Tracer()
        return _global
