"""Device milliseconds a scoring call spends digitizing its rows (self
time under ``dmlc.bin``), per call."""

from benchmark.metrics import _spans


def read(ctx):
    t = _spans.scope_seconds(ctx, lambda s: s == "dmlc.bin")
    return _spans.per(t, len(ctx.op_seconds), 1e3)
