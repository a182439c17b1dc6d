"""Seconds per ingest operation in which the device idles while the host
streams the slabs (idle inside the program's ``dmlc.ingest.stream`` span:
the first ``put_wait``, slab 0 landing behind the whole-matrix put the
cut sort is waiting for)."""

from benchmark.metrics import _spans


def read(ctx):
    return _spans.idle_seconds(ctx, "dmlc.ingest.stream")
