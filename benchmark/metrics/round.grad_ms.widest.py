"""Device milliseconds of a boosting round under the WIDEST bucket of the
ranking gradient alone (the largest ``w`` of
``dmlc.round.grad.rank.w<w>``): what the longest queries cost everyone,
per round."""

import re

from benchmark.metrics import _spans

_BUCKET = re.compile(r"^dmlc\.round\.grad\.rank\.w(\d+)$")


def read(ctx):
    widths = [int(m.group(1)) for m in map(_BUCKET.match, _spans.by_scope(ctx))
              if m]
    if not widths:
        return None
    t = _spans.scope_seconds(
        ctx, lambda s: s == f"dmlc.round.grad.rank.w{max(widths)}")
    return _spans.per(t, sum(ctx.op_work), 1e3)
