"""Device seconds a paged ingest operation spends in the streaming
sketch (self time under the program's ``dmlc.sketch.*`` scopes: a
summary a slab, the ladder's merges, the final collapse and the cuts),
per operation."""

from benchmark.metrics import _spans


def read(ctx):
    t = _spans.scope_seconds(ctx, lambda s: s.startswith("dmlc.sketch"))
    return _spans.per(t, len(ctx.op_seconds))
