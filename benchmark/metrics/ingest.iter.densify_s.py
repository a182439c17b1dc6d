"""Seconds per paged ingest operation spent scattering CSR pages into
float32 slabs on the host (wall of the program's
``dmlc.ingest.iter.densify`` spans, both passes)."""

from benchmark.metrics import _span_wall


def read(ctx):
    return _span_wall.wall_seconds(ctx, "dmlc.ingest.iter.densify")
