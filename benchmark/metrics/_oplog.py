"""The program's per-operation record of its spans: the view no trace
gives.

``profiler.op_log()`` (``doc/observability.md`` of the repo) holds one
record for every ``make_device_data``, ``fit_device`` and ``predict`` the
process has run — set-up's included, which the device trace, opened after
set-up, never sees — with the wall of each child span folded in by name
as ``[n, seconds, max_seconds, bytes]``, on the host's clock and with no
tracer slowing the host.  The log and the trace share no clock; they
share ``op``.  The smallest ``op`` among the trace's spans that OPEN an
operation is the window's first operation, so every record with a smaller
``op`` is set-up's, and every record with a larger ``op`` than the
trace's largest is the check's.  (``dmlc.compile`` opens nothing: a
worker may still be compiling for an earlier ``op``.)

This is the second module of the benchmark that imports ``dmlc_core_tpu``
(``system.py`` says it is the only one; it cannot be edited by the PR
that adds this file, and the next ``benchmark`` issue moves the fetch
there).  A program without ``op_log`` (the parent of that PR) gives no
log, and every reader built on this returns ``None``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark.metrics import _spans

Record = Dict[str, Any]

#: the spans that open an operation, and draw a fresh ``op`` doing so
OPENERS = ("dmlc.ingest", "dmlc.fit", "dmlc.predict")


@dataclasses.dataclass
class Parts:
    setup: List[Record]
    window: List[Record]
    check: List[Record]


def fetch() -> Optional[Tuple[List[Record], int]]:
    """The program's log and how many records its ring has overwritten;
    None where the program keeps no log."""
    from dmlc_core_tpu.utils import profiler

    if not hasattr(profiler, "op_log"):
        return None
    return profiler.op_log(), profiler.op_log_dropped()


def split(records: Sequence[Record], spans: Sequence[_spans.Span]
          ) -> Optional[Parts]:
    """The log cut by ``op`` against a trace's spans; None if the trace
    holds no span that opens an operation."""
    opened = [op for name, _a, _b, op in spans
              if name in OPENERS and op is not None]
    if not opened:
        return None
    first = min(opened)
    last = max(op for _n, _a, _b, op in spans if op is not None)
    return Parts(setup=[r for r in records if r["op"] < first],
                 window=[r for r in records if first <= r["op"] <= last],
                 check=[r for r in records if r["op"] > last])


def line(rec: Record) -> str:
    """One record as a run prints it: name, wall, counts, then each
    child's ``n x seconds (max)``."""
    counts = " ".join(f"{k}={v}" for k, v in rec["counts"].items())
    children = ", ".join(
        f"{name.removeprefix(rec['name'] + '.')} "
        f"{n} x {seconds:.4f} ({longest:.4f})"
        + (f" {nbytes / 1e9:.3f} GB" if nbytes else "")
        for name, (n, seconds, longest, nbytes) in rec["children"].items())
    comp = rec["compile"]
    verdicts = " ".join(f"{k}={v}" for k, v in comp.items()
                        if k != "seconds" and v)
    return (f"[oplog] op {rec['op']} {rec['name']} "
            f"{rec['end'] - rec['start']:.4f} s {counts}: {children}"
            + (f"; compile {verdicts} {comp['seconds']:.4f} s"
               if verdicts else ""))


def parts(ctx) -> Optional[Parts]:
    """This run's log in its three parts, cut once; set-up's records are
    printed as they are first asked for.  None on a program without a
    log, under a trace without the program's spans, and where the ring
    has overwritten records: the oldest go first, and those are
    set-up's."""
    if "_oplog.parts" not in ctx.state:
        got, log = None, fetch()
        if log is not None and log[1]:
            ctx.say(f"[oplog] the ring overwrote {log[1]} records: "
                    f"set-up's are not all there, no reading")
        elif log is not None:
            got = split(log[0], _spans.marks(ctx).spans)
            for rec in (got.setup if got else []):
                ctx.say(line(rec))
        ctx.state["_oplog.parts"] = got
    return ctx.state["_oplog.parts"]


def setup_seconds(ctx, name: str, *children: str) -> Optional[float]:
    """Seconds of set-up's operations named ``name``: their wall, or,
    given ``children``, of the child spans so named inside them.  None
    if set-up ran no such operation."""
    got = parts(ctx)
    recs = [r for r in (got.setup if got else []) if r["name"] == name]
    if not recs:
        return None
    if not children:
        return sum(r["end"] - r["start"] for r in recs)
    return sum(r["children"][c][1] for r in recs for c in children
               if c in r["children"])


def setup_compile_seconds(ctx) -> Optional[float]:
    """Seconds of the compile spans that carried a set-up ``op``, on
    whichever thread, folded into their operation or outliving it."""
    got = parts(ctx)
    if got is None or not got.setup:
        return None
    return sum(r["compile"]["seconds"] for r in got.setup)
