"""Device milliseconds of a boosting round spent on the categorical
columns' split scan (self time under the levels'
``dmlc.round.L<d>.split.cat`` scopes: the sort of every node's bins by
``G / (H + lambda)``, the two-ended prefix sums, the chosen set), per
round.  A program without categorical columns has no such scope and the
metric is left out."""

import re

from benchmark.metrics import _spans

_SCOPE = re.compile(r"^dmlc\.round\.L\d+\.split\.cat$")


def read(ctx):
    t = _spans.scope_seconds(ctx, lambda s: bool(_SCOPE.match(s)))
    return _spans.per(t, sum(ctx.op_work), 1e3)
