"""The trace reduction: from the ``.xplane.pb`` that ``jax.profiler`` wrote
to busy and idle intervals, time by operation name, and gaps.

Two halves.  :func:`load` opens the file (``jax.profiler.ProfileData``,
nothing but jax) and returns plain lists; :func:`summarize` is pure
arithmetic on those lists, so the self-test drives it with a small
synthetic event list.  Every per-layer metric is a small file that reads
the :class:`Summary` this returns.

Times are seconds on the trace's own clock.  An event is
``(name, start_s, end_s)``.  On a device plane the ``XLA Ops`` line holds
one event per executed operation, nested where one operation contains
others (a ``while`` and its body): an operation's SELF time is its span
minus what its children cover, so shares never count a second twice.
The ``XLA Modules`` line holds one event per executed program.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]
Interval = Tuple[float, float]

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host spans the harness writes around every operation of the window
SPAN_PREFIX = "bench."


# -- interval arithmetic -------------------------------------------------------

def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return float(sum(b - a for a, b in intervals))


def busy_within(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Seconds of a merged interval list that fall inside ``[lo, hi]``."""
    return length(clip(merged, lo, hi))


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]``: what ``merged`` leaves open."""
    out = []
    at = lo
    for a, b in clip(merged, lo, hi):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Self time by name of properly nested events of one line."""
    total: Dict[str, float] = {}
    stack: List[List] = []            # [name, end, self]

    def close():
        name, _end, self_s = stack.pop()
        total[name] = total.get(name, 0.0) + max(self_s, 0.0)

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            close()
        dur = end - start
        if stack:
            stack[-1][2] -= dur
        stack.append([name, end, dur])
    while stack:
        close()
    return total


# -- the summary ---------------------------------------------------------------

@dataclasses.dataclass
class DeviceSummary:
    name: str
    busy: List[Interval]              # merged, clipped to the window
    busy_s: float
    op_self_s: Dict[str, float]       # self time by operation name
    modules: List[Event]              # executed programs, in order


@dataclasses.dataclass
class Summary:
    window: Interval
    devices: List[DeviceSummary]
    spans: List[Event]                # the harness's host spans

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def op_seconds(self, match) -> float:
        """Self time of the operations whose name ``match`` accepts,
        averaged over the devices."""
        return sum(s for d in self.devices for n, s in d.op_self_s.items()
                   if match(n)) / len(self.devices)

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        acc: Dict[str, float] = {}
        for d in self.devices:
            for n, s in d.op_self_s.items():
                acc[n] = acc.get(n, 0.0) + s / len(self.devices)
        return sorted(acc.items(), key=lambda kv: -kv[1])[:k]

    def span_at(self, t: float) -> str:
        """Innermost harness span that covers ``t``, else ``outside``."""
        best: Optional[Event] = None
        for ev in self.spans:
            if ev[1] <= t < ev[2] and (best is None or ev[1] >= best[1]):
                best = ev
        return best[0] if best else "outside"

    def spans_named(self, name: str) -> List[Event]:
        lo, hi = self.window
        return sorted((ev for ev in self.spans
                       if ev[0] == name and ev[1] >= lo and ev[2] <= hi),
                      key=lambda e: e[1])

    def idle_in_spans(self, name: str, device: int = 0) -> List[float]:
        """Of each span of that name: its length less the device's busy
        time inside it."""
        busy = self.devices[device].busy
        return [(b - a) - busy_within(busy, a, b)
                for _n, a, b in self.spans_named(name)]

    def busy_share_in_spans(self, name: str) -> Optional[float]:
        """Percent of the spans' time in which the device ran an
        operation, averaged over the devices."""
        spans = self.spans_named(name)
        total = sum(b - a for _n, a, b in spans)
        if not total:
            return None
        busy = sum(busy_within(d.busy, a, b) for d in self.devices
                   for _n, a, b in spans) / len(self.devices)
        return 100.0 * busy / total

    def idle_gaps(self, device: int = 0, longest: int = 500
                  ) -> List[Tuple[str, float]]:
        """The ``longest`` idle gaps of one device's timeline, each named
        by what the host was doing (the harness span at the gap's middle)
        and by the programs on either side, with its length."""
        d = self.devices[device]
        mods = sorted(d.modules, key=lambda e: e[1])
        starts = [m[1] for m in mods]
        by_end = sorted(mods, key=lambda e: e[2])
        ends = [m[2] for m in by_end]
        out = []
        for lo, hi in sorted(gaps(d.busy, *self.window),
                             key=lambda g: g[0] - g[1])[:longest]:
            i = bisect.bisect_right(ends, lo + 1e-9)
            j = bisect.bisect_left(starts, hi - 1e-9)
            name = (f"{self.span_at(0.5 * (lo + hi))}:"
                    f"{by_end[i - 1][0] if i else 'start'}"
                    f"->{mods[j][0] if j < len(mods) else 'end'}")
            out.append((name, hi - lo))
        return out

    def top_gaps(self, k: int = 10) -> List[Tuple[str, float]]:
        """The longest idle gaps, gaps of one name added together."""
        acc: Dict[str, float] = {}
        for name, s in self.idle_gaps():
            acc[name] = acc.get(name, 0.0) + s
        return sorted(acc.items(), key=lambda kv: -kv[1])[:k]

    def module_gaps(self, match, device: int = 0) -> List[float]:
        """Device-idle seconds between consecutive executions of the
        programs whose name ``match`` accepts (the idle part only: busy
        time of other programs in between does not count)."""
        d = self.devices[device]
        mods = sorted((m for m in d.modules if match(m[0])),
                      key=lambda e: e[1])
        return [length(gaps(d.busy, a[2], b[1]))
                for a, b in zip(mods[:-1], mods[1:]) if b[1] > a[2]]


def summarize(planes: Dict[str, Dict[str, List[Event]]],
              window: Optional[Interval] = None) -> Summary:
    """``planes`` maps a plane name to its lines, a line name to its
    events.  ``window`` defaults to the outermost ``bench.window`` span,
    else to the extent of the device events."""
    spans = [ev for pname, lines in planes.items()
             if not pname.startswith(DEVICE_PLANE_PREFIX)
             for evs in lines.values() for ev in evs
             if ev[0].startswith(SPAN_PREFIX)]
    dev_names = sorted((p for p in planes if p.startswith(DEVICE_PLANE_PREFIX)),
                       key=lambda p: int(p[len(DEVICE_PLANE_PREFIX):].split()[0]))
    if not dev_names:
        raise ValueError("the trace holds no device plane")
    if window is None:
        wins = [ev for ev in spans if ev[0] == SPAN_PREFIX + "window"]
        if wins:
            window = (min(w[1] for w in wins), max(w[2] for w in wins))
        else:
            evs = [ev for p in dev_names
                   for ev in planes[p].get(OPS_LINE, [])]
            if not evs:
                raise ValueError("no device operation in the trace")
            window = (min(e[1] for e in evs), max(e[2] for e in evs))
    lo, hi = window
    devices = []
    for p in dev_names:
        ops = [(n, max(a, lo), min(b, hi)) for n, a, b
               in planes[p].get(OPS_LINE, []) if min(b, hi) > max(a, lo)]
        busy = merge((a, b) for _n, a, b in ops)
        mods = [(n, a, b) for n, a, b in planes[p].get(MODULES_LINE, [])
                if min(b, hi) > max(a, lo)]
        devices.append(DeviceSummary(p, busy, length(busy),
                                     self_times(ops), mods))
    return Summary(window, devices, spans)


# -- reading the file ------------------------------------------------------------

_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_LAYOUT = re.compile(r"\{[^{}]*\}")
_FINGERPRINT = re.compile(r"\(\d+\)$")


def short_name(text: str) -> str:
    """An operation's name from the HLO text the TPU trace carries as the
    event name: ``<result> <opcode>[/<custom-call target>] <result
    shape>``, e.g. ``closed_call.45 custom-call/tpu_custom_call
    (f32[32,64,128], ...``.  Text that is not HLO is left as it is."""
    lhs, sep, rhs = text.partition(" = ")
    if not sep:
        return text[:96]
    op = _OPCODE.search(" " + rhs)
    if not op:
        return text[:96]
    shape = _LAYOUT.sub("", rhs[:max(op.start() - 1, 0)]).strip()
    target = _TARGET.search(rhs)
    name = lhs.lstrip("%") + " " + op.group(1)
    if target:
        name += "/" + target.group(1)
    return (name + " " + shape)[:96]


def newest_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """Device planes whole; of the host planes only the harness's spans
    (a host thread's line can hold a million python frames)."""
    from jax.profiler import ProfileData

    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            tidy = (short_name if line.name == OPS_LINE
                    else lambda n: _FINGERPRINT.sub("", n))
            evs = [(tidy(ev.name) if device else ev.name,
                    ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9)
                   for ev in line.events
                   if device or ev.name.startswith(SPAN_PREFIX)]
            if evs:
                lines.setdefault(line.name, []).extend(evs)
        if lines:
            planes[plane.name] = lines
    return planes
