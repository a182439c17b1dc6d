"""Device milliseconds of a boosting round under the program's
``dmlc.round.L<d>.sync`` scopes (``hist_sync``: the ``psum`` of the built
histograms over ``data``, every level), averaged over the chips, per
round: the collective and the wait for the slowest chip, the inside twin
of ``psum.ms_per_round``.  Nothing to read on one chip."""

import re

from benchmark.metrics import _spans

_SYNC = re.compile(r"^dmlc\.round\.L\d+\.sync$")


def read(ctx):
    if len(ctx.summary.devices) < 2:
        return None
    return _spans.per(_spans.scope_seconds(ctx, _SYNC.match),
                      sum(ctx.op_work), 1e3)
