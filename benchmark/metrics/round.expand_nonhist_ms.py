"""Device milliseconds of a leaf-wise round that its expansions spend
OUTSIDE the histogram builds: self time under ``dmlc.round.expand.pick``
and ``dmlc.round.expand.settle``, per round — what a faster build would
uncover.  A program without the scopes gives nothing."""

from benchmark.metrics import _spans


def read(ctx):
    t = _spans.scope_seconds(ctx, lambda s: s in (
        "dmlc.round.expand.pick", "dmlc.round.expand.settle"))
    return _spans.per(t, sum(ctx.op_work), 1e3)
