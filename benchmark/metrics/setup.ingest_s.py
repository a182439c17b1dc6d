"""Seconds of set-up inside ``make_device_data``: the wall of set-up's
``dmlc.ingest`` operations, from the program's own record
(``_oplog``), to the last staging call's enqueue."""

from benchmark.metrics import _oplog


def read(ctx):
    return _oplog.setup_seconds(ctx, "dmlc.ingest")
