"""Milliseconds of a scoring call in which the device idles while the
host copies the answers back (idle inside ``dmlc.predict.fetch.copy``,
the part of ``dmlc.predict.fetch`` after the wait for the device; median
over the calls)."""

from benchmark.metrics import _spans


def read(ctx):
    return _spans.per(_spans.idle_seconds(ctx, "dmlc.predict.fetch.copy"),
                      1, 1e3)
