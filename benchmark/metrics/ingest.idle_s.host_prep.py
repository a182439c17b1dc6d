"""Seconds per ingest operation in which the device idles while the host
makes the arrays contiguous, folds weights and scans for NaN (idle inside
the program's ``dmlc.ingest.host_prep`` span)."""

from benchmark.metrics import _spans


def read(ctx):
    return _spans.idle_seconds(ctx, "dmlc.ingest.host_prep")
