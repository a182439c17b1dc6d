"""Milliseconds of a scoring call in which the device idles while the
host stacks, pads and puts the trees (idle inside ``dmlc.predict.stack``;
median over the calls)."""

from benchmark.metrics import _spans


def read(ctx):
    return _spans.per(_spans.idle_seconds(ctx, "dmlc.predict.stack"), 1, 1e3)
