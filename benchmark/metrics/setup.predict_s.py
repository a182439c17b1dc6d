"""Seconds of set-up inside ``predict``: the wall of set-up's
``dmlc.predict`` operations (the warm call, which builds the device
forest)."""

from benchmark.metrics import _oplog


def read(ctx):
    return _oplog.setup_seconds(ctx, "dmlc.predict")
