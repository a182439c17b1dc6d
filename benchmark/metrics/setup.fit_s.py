"""Seconds of set-up inside ``fit_device``: the wall of set-up's
``dmlc.fit`` operations (the warm fit; the score cell's 100 rounds)."""

from benchmark.metrics import _oplog


def read(ctx):
    return _oplog.setup_seconds(ctx, "dmlc.fit")
