"""Device seconds an ingest operation spends on the summary of the values
its columns have (self time under the program's ``dmlc.cuts.finite``
scope: the count of the values, the key-only sort, the reads of the
quantile points), per operation.  The scope lies inside ``dmlc.cuts``, so
``ingest.cuts_device_s`` holds the rest (the merge into cuts).  A program
without the scope gives nothing."""

from benchmark.metrics import _spans


def read(ctx):
    t = _spans.scope_seconds(ctx, lambda s: s == "dmlc.cuts.finite")
    return _spans.per(t, len(ctx.op_seconds))
