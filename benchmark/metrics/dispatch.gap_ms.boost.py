"""Median device-idle gap between consecutive round programs: what the
host's dispatch and the fetch of a chunk's trees leave open."""

from benchmark import stats
from benchmark.metrics import _names


def read(ctx):
    gaps = ctx.summary.module_gaps(_names.is_round_program)
    return 1e3 * stats.median(gaps) if gaps else None
