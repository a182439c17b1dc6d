"""Milliseconds of a scoring call in which the device idles while the
host puts the rows and enqueues the programs (idle inside
``dmlc.predict.put`` and ``dmlc.predict.dispatch``; median over the
calls)."""

from benchmark.metrics import _spans


def read(ctx):
    return _spans.per(_spans.idle_seconds(
        ctx, "dmlc.predict.put", "dmlc.predict.dispatch"), 1, 1e3)
