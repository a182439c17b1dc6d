"""Seconds of set-up's ingests in ``dmlc.ingest.stream``: the slabs'
``put``, ``put_wait`` and ``bin_dispatch`` — where the host waits for the
puts (on four chips, behind the whole-matrix put of the cut sort)."""

from benchmark.metrics import _oplog


def read(ctx):
    return _oplog.setup_seconds(ctx, "dmlc.ingest", "dmlc.ingest.stream")
