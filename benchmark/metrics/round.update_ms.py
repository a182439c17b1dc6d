"""Device milliseconds of a boosting round in the margin update (self
time under ``dmlc.round.update``: the round's leaf values added onto the
margins — under ``multi:*`` K trees' deltas onto ``[K, n]`` margins at
once), per round."""

from benchmark.metrics import _spans


def read(ctx):
    t = _spans.scope_seconds(ctx, lambda s: s == "dmlc.round.update")
    return _spans.per(t, sum(ctx.op_work), 1e3)
