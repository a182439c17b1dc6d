"""Device milliseconds a scoring call spends walking the trees (self time
under ``dmlc.descend``: ``_predict_trees``), per call."""

from benchmark.metrics import _spans


def read(ctx):
    t = _spans.scope_seconds(ctx, lambda s: s == "dmlc.descend")
    return _spans.per(t, len(ctx.op_seconds), 1e3)
