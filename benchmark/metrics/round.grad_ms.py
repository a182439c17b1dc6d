"""Device milliseconds of a boosting round in the gradient stage: self
time under every scope that starts with ``dmlc.round.grad`` (a device
event counts under its INNERMOST ``dmlc.*`` scope, so a ranking round's
``dmlc.round.grad.rank`` and its buckets' ``.w<width>`` scopes are named
here one by one), per round."""

from benchmark.metrics import _spans


def read(ctx):
    t = _spans.scope_seconds(ctx, lambda s: s.startswith("dmlc.round.grad"))
    return _spans.per(t, sum(ctx.op_work), 1e3)
