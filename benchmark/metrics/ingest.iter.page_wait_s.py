"""Seconds per paged ingest operation in which the consumer waits on the
page reader (wall of the program's ``dmlc.ingest.iter.page_wait`` spans,
both passes: ``DiskRowIter.next_block`` on the thread that densifies)."""

from benchmark.metrics import _span_wall


def read(ctx):
    return _span_wall.wall_seconds(ctx, "dmlc.ingest.iter.page_wait")
