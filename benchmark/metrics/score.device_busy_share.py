"""Share of the scoring calls' wall in which the device ran an
operation: the rest is put, dispatch and fetch on the host, in series with the device."""


def read(ctx):
    return ctx.summary.busy_share_in_spans("bench.op")
