"""Seconds of set-up's ingests in ``dmlc.ingest.host_prep``: contiguity,
the weight fold and the NaN scan of the whole matrix, the device idle."""

from benchmark.metrics import _oplog


def read(ctx):
    return _oplog.setup_seconds(ctx, "dmlc.ingest", "dmlc.ingest.host_prep")
