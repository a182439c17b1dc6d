"""Seconds of set-up's ingests in ``dmlc.ingest.host_prep.regroup``: the
stable sort by ``qid``, the one copy of the rows into query order and the
group table, the device idle."""

from benchmark.metrics import _oplog


def read(ctx):
    got = _oplog.parts(ctx)
    recs = [r["children"]["dmlc.ingest.host_prep.regroup"][1]
            for r in (got.setup if got else [])
            if "dmlc.ingest.host_prep.regroup" in r["children"]]
    return sum(recs) if recs else None
