"""Seconds per paged ingest operation in the two host passes over every
slab that ``make_device_data_iter`` makes beside the densify: its copy of
the staging buffer (``dmlc.ingest.iter.copy``, both passes) and the scan
for NaN (``dmlc.ingest.iter.nan_scan``, the sketch pass)."""

from benchmark.metrics import _span_wall


def read(ctx):
    return _span_wall.wall_seconds(ctx, "dmlc.ingest.iter.copy",
                                   "dmlc.ingest.iter.nan_scan")
