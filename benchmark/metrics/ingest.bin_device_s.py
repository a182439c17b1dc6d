"""Device seconds an ingest operation spends digitizing (self time under
the program's ``dmlc.bin`` scope: ``apply_bins``, whatever it is made
of), per operation."""

from benchmark.metrics import _spans


def read(ctx):
    t = _spans.scope_seconds(ctx, lambda s: s == "dmlc.bin")
    return _spans.per(t, len(ctx.op_seconds))
