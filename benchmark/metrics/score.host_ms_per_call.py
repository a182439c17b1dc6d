"""Median over the scoring calls of the call's wall less the device's
busy time inside it: what the host adds to every call."""

from benchmark import stats


def read(ctx):
    per_call = ctx.summary.idle_in_spans("bench.op")
    return 1e3 * stats.median(per_call) if per_call else None
