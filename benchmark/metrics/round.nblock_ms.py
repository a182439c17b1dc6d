"""Device milliseconds of a boosting round spent on what building a
histogram in node blocks adds outside the kernels (self time under the
program's ``dmlc.hist.nblock`` scope: each block's map of the node ids
onto ``0..nb-1`` and the join of the blocks' histograms), per round.  A
program that builds no histogram in node blocks has no such scope and the
metric is left out."""

from benchmark.metrics import _spans


def read(ctx):
    t = _spans.scope_seconds(ctx, lambda s: s == "dmlc.hist.nblock")
    return _spans.per(t, sum(ctx.op_work), 1e3)
