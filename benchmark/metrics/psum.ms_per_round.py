"""Device time of the all-reduce operations on one chip's timeline, per
boosting round.  Nothing to read on one chip."""

from benchmark.metrics import _names


def read(ctx):
    ops = ctx.summary.devices[0].op_self_s
    t = sum(s for n, s in ops.items() if _names.is_all_reduce(n))
    rounds = sum(ctx.op_work)
    return 1e3 * t / rounds if t and rounds else None
