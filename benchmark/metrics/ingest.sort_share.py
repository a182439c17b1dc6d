"""Share of the device's busy time that the sort operations of the cut
computation take (``jnp.quantile`` over the whole matrix)."""

from benchmark.metrics import _names


def read(ctx):
    s = ctx.summary
    return 100.0 * s.op_seconds(_names.is_sort) / s.busy_s
