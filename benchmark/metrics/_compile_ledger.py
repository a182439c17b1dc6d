"""The program's compile ledger: which programs set-up traced, lowered,
compiled or read back from the persistent cache, on whose thread and under
which span.

Since PR 53 every record of ``profiler.op_log()`` carries ``programs``:
one entry for each program jax reported on a thread that had the operation
open, with ``trace_s`` (Python to jaxpr), ``lower_s`` (jaxpr to MLIR),
``backend_s`` (everything after: the cache key, the read or XLA and the
write) and inside it ``read_s`` (the persistent cache's read,
deserialisation and load), the ``verdict`` of that program's own cache
traffic, ``thread`` = ``"own"`` (the thread that opened the operation: on
its critical path) or ``"joined"`` (a ``BackgroundCompiler`` worker) and
``under`` = the innermost span open there.  Set-up is never under the
profiler, so these are the host's own seconds.  The ``setup.compile_*``
readers sum them over SET-UP's records (``_oplog.parts``); a program whose
records have no ``programs`` (the parent of that PR) gives None.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

from benchmark.metrics import _oplog

Entry = Dict[str, Any]


def line(rec: _oplog.Record, entry: Entry) -> str:
    """One program as a run prints it."""
    count = f" x{entry['n']}" if "n" in entry else ""
    return (f"[compile] op {rec['op']} {rec['name']} under {entry['under']} "
            f"{entry['thread']} {entry['program']}{count} {entry['verdict']} "
            f"trace {entry['trace_s']:.3f} lower {entry['lower_s']:.3f} "
            f"read {entry['read_s']:.3f} "
            f"rest {entry['backend_s'] - entry['read_s']:.3f}")


def entries(ctx) -> Optional[List[Tuple[_oplog.Record, Entry]]]:
    """Set-up's programs, each beside its record, printed one line a
    program as they are first asked for.  None where set-up's records
    cannot be read or none of them has ``programs``."""
    if "_compile_ledger.entries" not in ctx.state:
        got = _oplog.parts(ctx)
        found = None
        if got is not None and any("programs" in r for r in got.setup):
            found = [(r, p) for r in got.setup
                     for p in r.get("programs", ())]
            for rec, entry in found:
                ctx.say(line(rec, entry))
        ctx.state["_compile_ledger.entries"] = found
    return ctx.state["_compile_ledger.entries"]


def total(ctx, seconds: Callable[[Entry], float],
          thread: Optional[str] = None) -> Optional[float]:
    """``seconds(entry)`` summed over set-up's programs, those of
    ``thread`` alone where it is given."""
    found = entries(ctx)
    if found is None:
        return None
    return sum(seconds(p) for _rec, p in found
               if thread is None or p["thread"] == thread)
