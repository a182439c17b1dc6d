"""Wall of the program's own host spans, per operation: what a phase of
the HOST costs an operation, device busy or not (``_spans.idle_seconds``
is the device's view of the same spans).  A program without the span
gives nothing."""

from __future__ import annotations

from typing import Dict, Optional

from benchmark import stats
from benchmark.metrics import _spans


def wall_seconds(ctx, *names: str) -> Optional[float]:
    """Median over the window's operations of the summed wall of the
    spans named, each counted as far as it lies in the window; None if
    the trace holds no such span."""
    lo, hi = ctx.summary.window
    per_op: Dict[object, float] = {}
    for i, (name, a, b, op) in enumerate(_spans.marks(ctx).spans):
        a, b = max(a, lo), min(b, hi)
        if name in names and b > a:
            key = ("span", i) if op is None else op
            per_op[key] = per_op.get(key, 0.0) + (b - a)
    return stats.median(list(per_op.values())) if per_op else None
