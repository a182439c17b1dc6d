"""Share of the ingest operations' wall in which the device ran an
operation: the rest is the host slicing, putting and waiting."""


def read(ctx):
    return ctx.summary.busy_share_in_spans("bench.op")
