"""Device milliseconds of a boosting round spent choosing splits (self
time under the levels' ``dmlc.round.L<d>.split`` scopes: the cumulative
sums over the bins and the gain of every threshold — on a table with
holes each of them twice, the missing mass on either side), per round."""

import re

from benchmark.metrics import _spans

_LEVEL_SPLIT = re.compile(r"^dmlc\.round\.L\d+\.split$")


def read(ctx):
    t = _spans.scope_seconds(ctx, lambda s: bool(_LEVEL_SPLIT.match(s)))
    return _spans.per(t, sum(ctx.op_work), 1e3)
