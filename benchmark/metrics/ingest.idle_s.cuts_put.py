"""Seconds per ingest operation in which the device idles inside the
program's ``dmlc.ingest.cuts`` span: the put of the whole float32 matrix
for the cut computation, before its first program starts."""

from benchmark.metrics import _spans


def read(ctx):
    return _spans.idle_seconds(ctx, "dmlc.ingest.cuts")
