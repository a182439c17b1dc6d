"""Device seconds an ingest operation spends settling whether the table
has holes: self time under the program's ``dmlc.cuts.nan_scan`` scope
(since PR 50 ``ops.quantile.nan_scan``, ONE fusion over the matrix the cut
sort's put has laid on the device: per column the NaN count and whether
any value is finite; the host waits for it inside its
``dmlc.ingest.cuts.nan_scan`` span), per operation.  A program without
the scope gives nothing."""

from benchmark.metrics import _spans


def read(ctx):
    t = _spans.scope_seconds(ctx, lambda s: s == "dmlc.cuts.nan_scan")
    return _spans.per(t, len(ctx.op_seconds))
