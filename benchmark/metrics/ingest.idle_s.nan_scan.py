"""Seconds per ingest operation in which the device idles while the host
scans the matrix for NaN and, on a table with holes, its columns for a
finite value (idle inside the program's
``dmlc.ingest.host_prep.nan_scan`` span, a part of ``host_prep``).  A
program without the span gives nothing."""

from benchmark.metrics import _spans


def read(ctx):
    return _spans.idle_seconds(ctx, "dmlc.ingest.host_prep.nan_scan")
