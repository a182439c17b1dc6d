"""Device milliseconds of a boosting round spent on set membership (self
time under the levels' ``dmlc.round.L<d>.route.cat`` scopes and the leaf
tail's ``dmlc.round.leaf.route.cat``: each row's bin looked up in its
node's set, the per-row cost of a categorical split), per round.  A
program without categorical columns has no such scope and the metric is
left out."""

import re

from benchmark.metrics import _spans

_SCOPE = re.compile(r"^dmlc\.round\.(L\d+|leaf)\.route\.cat$")


def read(ctx):
    t = _spans.scope_seconds(ctx, lambda s: bool(_SCOPE.match(s)))
    return _spans.per(t, sum(ctx.op_work), 1e3)
