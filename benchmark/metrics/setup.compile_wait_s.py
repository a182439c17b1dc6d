"""Seconds set-up's fits spend in ``dmlc.fit.join_warmup`` and
``dmlc.fit.warm_dispatch``: what compiling, or reading the cache, costs
the critical path once the ingest has stopped hiding it (with the warm
execution and its sync)."""

from benchmark.metrics import _oplog


def read(ctx):
    return _oplog.setup_seconds(ctx, "dmlc.fit", "dmlc.fit.join_warmup",
                                "dmlc.fit.warm_dispatch")
