"""Device milliseconds of a boosting round outside the histogram
kernels: the device's busy time less ``round.hist_ms``, per round."""

from benchmark.metrics import _spans


def read(ctx):
    hist = _spans.hist_seconds(ctx)
    if hist is None:
        return None
    return _spans.per(ctx.summary.busy_s - hist, sum(ctx.op_work), 1e3)
