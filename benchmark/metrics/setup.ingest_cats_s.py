"""Seconds of set-up's ingests in ``dmlc.ingest.cats``: the scan of the
categorical columns' codes, two vectors fetched, and the enqueue of the
counts and the category→bin tables (the host waits for the scan, so the
put it follows shows here where nothing else waited for it).  A program
without the span gives nothing."""

from benchmark.metrics import _oplog


def read(ctx):
    got = _oplog.parts(ctx)
    recs = [r["children"]["dmlc.ingest.cats"][1]
            for r in (got.setup if got else [])
            if "dmlc.ingest.cats" in r["children"]]
    return sum(recs) if recs else None
