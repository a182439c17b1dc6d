"""Seconds of set-up spent building the ``DiskRowIter`` page cache: the
wall of set-up's ``dmlc.pages.build`` operations, from the program's own
record (``_oplog``) — rows pushed into pages and pages written to local
disk, once a data set."""

from benchmark.metrics import _oplog


def read(ctx):
    return _oplog.setup_seconds(ctx, "dmlc.pages.build")
