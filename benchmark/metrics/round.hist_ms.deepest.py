"""Device milliseconds of a boosting round inside the histogram kernel of
the deepest level alone (the largest ``d`` of ``dmlc.round.L<d>.hist``),
per round."""

from benchmark.metrics import _spans


def read(ctx):
    return _spans.per(_spans.hist_seconds(ctx, deepest_only=True),
                      sum(ctx.op_work), 1e3)
