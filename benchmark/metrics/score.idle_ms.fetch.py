"""Milliseconds of a scoring call in which the device idles while the
host waits for and copies back the answers (idle inside
``dmlc.predict.fetch``: what is left of the fetch once the device is
done; median over the calls)."""

from benchmark.metrics import _spans


def read(ctx):
    return _spans.per(_spans.idle_seconds(ctx, "dmlc.predict.fetch"), 1, 1e3)
