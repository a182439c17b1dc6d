"""The arithmetic that turns a window's operations into end-to-end
metrics.  Kept here so that no PR that claims a gain can change it."""

from __future__ import annotations

import math
from statistics import median  # noqa: F401 - the readers' median
from typing import List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100) of all the values: the
    smallest value with at least q% of the sample at or below it."""
    if not values:
        raise ValueError("percentile of an empty sample")
    s = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(s)))
    return s[min(k, len(s)) - 1]


def rate(work: Sequence[float], seconds: Sequence[float]) -> float:
    """Work over time, over ALL the operations of the window."""
    return float(sum(work)) / float(sum(seconds))


def end_to_end(spec: dict, work: List[float], seconds: List[float]) -> float:
    """One end-to-end metric from a mix file's ``end_to_end`` entry:
    ``{"kind": "rate"}`` is units of work per second of operation time,
    ``{"kind": "percentile", "q": 95, "scale": 1000}`` the q-th
    percentile of one operation's wall, times ``scale``."""
    kind = spec["kind"]
    if kind == "rate":
        return rate(work, seconds)
    if kind == "percentile":
        return percentile(seconds, spec["q"]) * spec.get("scale", 1.0)
    raise ValueError(f"unknown end-to-end kind {kind!r}")
