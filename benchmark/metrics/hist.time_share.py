"""Share of the device's busy time inside the histogram kernels."""

from benchmark.metrics import _names


def read(ctx):
    s = ctx.summary
    t = s.op_seconds(_names.is_hist_kernel)
    return 100.0 * t / s.busy_s if t else None
