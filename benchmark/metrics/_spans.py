"""The program's own marks in the trace: the inside view.

The program writes two kinds of mark (``doc/observability.md`` of the
repo lists them).  Host phases are ``profiler.span`` events named
``dmlc.*`` on the host planes, on the same clock as the device events and
the harness's ``bench.*`` spans, each with an ``op`` stat that all spans
of one operation share.  Device phases are ``jax.named_scope`` names in
the ``op_name`` of the compiled operations.  The TPU trace names a device
event by its HLO text WITHOUT that metadata; the ``op_name`` rides as the
``tf_op`` stat of the event's METADATA record, which
``jax.profiler.ProfileData`` does not show.  So the device planes are
read from the file's own encoding here (a few fields of the xplane
schema, below), and the scope of an event is the last ``dmlc.*``
component of its ``tf_op`` (a fusion carries the ``op_name`` of its
root, so a scope's time includes what XLA fused into it).

``xplane.load`` keeps neither mark (it drops host events not named
``bench.*`` and shortens device names), so this module opens the same
file again, keeps only those two things, and offers two reductions built
on ``xplane``'s own arithmetic.  Both are pure functions of plain lists:
the self-test drives them with a synthetic trace.  A program without the
marks (the parent of the PR that added them) gives empty lists and every
reader built on this returns ``None``.
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import (Callable, Dict, Iterator, List, Optional, Sequence,
                    Tuple)

from benchmark import harness, stats, xplane

#: (name, start_s, end_s, op): a host span of the program
Span = Tuple[str, float, float, Optional[int]]
#: (scope or "", start_s, end_s): a device operation under its scope
Scoped = Tuple[str, float, float]

HOST_PREFIX = "dmlc."
_SCOPE = re.compile(r"(?:^|/)(dmlc\.[A-Za-z0-9_.]+)")


def scope_of(op_name: str) -> str:
    """The innermost ``dmlc.*`` scope of an ``op_name`` (``""`` if it
    names none)."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else ""


@dataclasses.dataclass
class Marks:
    spans: List[Span]                 # host spans named dmlc.*
    device_ops: List[List[Scoped]]    # per device, in plane order


# -- the file's own encoding --------------------------------------------------------
# protobuf wire format, and of tsl/profiler/protobuf/xplane.proto only:
#   XSpace.planes = 1
#   XPlane: name = 2, lines = 3, event_metadata = 4 (map: key 1, value 2),
#           stat_metadata = 5 (map)
#   XLine: name = 2, timestamp_ns = 3, events = 4
#   XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3
#   XEventMetadata: name = 2, stats = 5;  XStatMetadata: name = 2
#   XStat: metadata_id = 1, str_value = 5, ref_value = 7 (a stat
#          metadata's name holds the string)
_TF_OP = "tf_op"


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int) -> Iterator[Tuple[int, int, int]]:
    """The fields of the message at ``buf[lo:hi]`` as (number, a, b): a
    varint field's value is ``a`` (``b`` is -1); a length-delimited or
    fixed field's bytes are ``buf[a:b]``.  Nothing is copied."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value, -1
            continue
        if kind == 2:
            size, i = _varint(buf, i)
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, i, i + size
        i += size


def _text(buf: bytes, lo: int, hi: int) -> str:
    return buf[lo:hi].decode("utf-8", "replace")


def _map_value(buf: bytes, lo: int, hi: int) -> Tuple[int, int, int]:
    """One entry of a map<int64, message>: its key and where the value
    lies."""
    key, at = 0, (lo, lo)
    for number, a, b in _fields(buf, lo, hi):
        if number == 1:
            key = a
        elif number == 2:
            at = (a, b)
    return key, at[0], at[1]


def device_ops_of(buf: bytes, lo: int, hi: int) -> List[Scoped]:
    """The ``XLA Ops`` events of the device plane at ``buf[lo:hi]``, each
    under its scope."""
    lines, event_meta, stat_names = [], {}, {}
    for number, a, b in _fields(buf, lo, hi):
        if number == 3:
            lines.append((a, b))
        elif number == 4:
            key, va, vb = _map_value(buf, a, b)
            event_meta[key] = (va, vb)
        elif number == 5:
            key, va, vb = _map_value(buf, a, b)
            stat_names[key] = next(
                (_text(buf, x, y) for n, x, y in _fields(buf, va, vb)
                 if n == 2), "")
    scope_by_id: Dict[int, str] = {}

    def scope(metadata_id: int) -> str:
        if metadata_id not in scope_by_id:
            found = ""
            va, vb = event_meta.get(metadata_id, (0, 0))
            for number, a, b in _fields(buf, va, vb):
                if number != 5:
                    continue
                stat = {n: (x, y) for n, x, y in _fields(buf, a, b)}
                if stat_names.get(stat.get(1, (0,))[0]) != _TF_OP:
                    continue
                if 5 in stat:
                    found = scope_of(_text(buf, *stat[5]))
                elif 7 in stat:
                    found = scope_of(stat_names.get(stat[7][0], ""))
            scope_by_id[metadata_id] = found
        return scope_by_id[metadata_id]

    ops: List[Scoped] = []
    for lo_l, hi_l in lines:
        name, t0_s, events = "", 0.0, []
        for number, a, b in _fields(buf, lo_l, hi_l):
            if number == 2:
                name = _text(buf, a, b)
            elif number == 3:
                t0_s = a * 1e-9
            elif number == 4:
                events.append((a, b))
        if name != xplane.OPS_LINE:
            continue
        for a, b in events:
            meta = offset = duration = 0
            for number, x, _y in _fields(buf, a, b):
                if number == 1:
                    meta = x
                elif number == 2:
                    offset = x
                elif number == 3:
                    duration = x
                else:
                    break                # stats follow: not needed
            start = t0_s + offset * 1e-12
            ops.append((scope(meta), start, start + duration * 1e-12))
    return ops


def load(path: str) -> Marks:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        buf = f.read()
    devices: List[Tuple[int, List[Scoped]]] = []
    for number, a, b in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name = next((_text(buf, x, y) for n, x, y in _fields(buf, a, b)
                     if n == 2), "")
        if name.startswith(xplane.DEVICE_PLANE_PREFIX):
            index = int(name[len(xplane.DEVICE_PLANE_PREFIX):].split()[0])
            devices.append((index, device_ops_of(buf, a, b)))
    spans: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(xplane.DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIX):
                    op = next((int(v) for k, v in ev.stats if k == "op"),
                              None)
                    spans.append((ev.name, ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9,
                                  op))
    return Marks(spans, [ops for _i, ops in sorted(devices,
                                                  key=lambda d: d[0])])


def marks(ctx) -> Marks:
    """The marks of this run's trace, read once."""
    if "_spans.marks" not in ctx.state:
        ctx.state["_spans.marks"] = load(xplane.newest_xplane(
            os.path.join(ctx.root, harness.TRACE_DIR, ctx.workload)))
    return ctx.state["_spans.marks"]


# -- the two reductions ----------------------------------------------------------

def idle_by_op(spans: Sequence[Span], names: Sequence[str],
               busy: Sequence[xplane.Interval], window: xplane.Interval
               ) -> List[float]:
    """Idle inside spans of a name: for every operation that has a span
    named in ``names``, those spans' wall less the device's busy time
    inside them, added up.  A span counts as far as it lies in the
    window (busy time is known only there)."""
    lo, hi = window
    per_op: Dict[object, float] = {}
    for i, (name, a, b, op) in enumerate(spans):
        a, b = max(a, lo), min(b, hi)
        if name in names and b > a:
            key = ("span", i) if op is None else op
            per_op[key] = per_op.get(key, 0.0) + (
                (b - a) - xplane.busy_within(busy, a, b))
    return list(per_op.values())


def self_seconds_by_scope(device_ops: Sequence[Sequence[Scoped]],
                          window: xplane.Interval) -> Dict[str, float]:
    """Device self time by scope (``xplane.self_times`` keyed by scope
    instead of by name: a ``while`` less its body), clipped to the
    window and averaged over the devices.  ``""`` is time under no
    scope."""
    lo, hi = window
    total: Dict[str, float] = {}
    for ops in device_ops:
        clipped = [(s, max(a, lo), min(b, hi)) for s, a, b in ops
                   if min(b, hi) > max(a, lo)]
        for scope, t in xplane.self_times(clipped).items():
            total[scope] = total.get(scope, 0.0) + t / len(device_ops)
    return total


# -- what the readers call ---------------------------------------------------------

def by_scope(ctx) -> Dict[str, float]:
    """Device self seconds of the window by scope, reduced once."""
    if "_spans.by_scope" not in ctx.state:
        ctx.state["_spans.by_scope"] = self_seconds_by_scope(
            marks(ctx).device_ops, ctx.summary.window)
    return ctx.state["_spans.by_scope"]


def scope_seconds(ctx, match: Callable[[str], bool]) -> Optional[float]:
    """Device seconds of the window under the scopes ``match`` accepts;
    None if the trace names no such scope."""
    hits = [t for s, t in by_scope(ctx).items() if s and match(s)]
    return sum(hits) if hits else None


def idle_seconds(ctx, *names: str) -> Optional[float]:
    """Median over the window's operations of the idle inside the spans
    named; None if the trace holds no such span."""
    per_op = idle_by_op(marks(ctx).spans, names,
                        ctx.summary.devices[0].busy, ctx.summary.window)
    return stats.median(per_op) if per_op else None


def per(value: Optional[float], count: float, scale: float = 1.0
        ) -> Optional[float]:
    """``value`` per unit of ``count`` (operations, calls, rounds)."""
    return None if value is None or not count else scale * value / count


_LEVEL_HIST = re.compile(r"^dmlc\.round\.L(\d+)\.hist$")


def hist_level(scope: str) -> Optional[int]:
    """The level d of ``dmlc.round.L<d>.hist``, else None."""
    m = _LEVEL_HIST.match(scope)
    return int(m.group(1)) if m else None


def hist_seconds(ctx, deepest_only: bool = False) -> Optional[float]:
    """Device seconds under the per-level histogram scopes: all levels,
    or the deepest alone."""
    levels = {hist_level(s) for s in by_scope(ctx)} - {None}
    if deepest_only and levels:
        levels = {max(levels)}
    return scope_seconds(ctx, lambda s: hist_level(s) in levels)
