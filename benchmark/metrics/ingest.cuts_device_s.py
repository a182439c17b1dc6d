"""Device seconds an ingest operation spends computing the cuts (self
time under ``dmlc.cuts``: summary, sort, merge), per operation."""

from benchmark.metrics import _spans


def read(ctx):
    t = _spans.scope_seconds(ctx, lambda s: s == "dmlc.cuts")
    return _spans.per(t, len(ctx.op_seconds))
