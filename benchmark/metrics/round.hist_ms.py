"""Device milliseconds of a boosting round inside the histogram kernels
(self time under the program's ``dmlc.round.L<d>.hist`` scopes, every
level), per round."""

from benchmark.metrics import _spans


def read(ctx):
    return _spans.per(_spans.hist_seconds(ctx), sum(ctx.op_work), 1e3)
