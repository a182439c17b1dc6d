"""Seconds of the ``dmlc.compile`` spans that carry a set-up ``op``:
compiling, or reading the persistent cache, on the ``BackgroundCompiler``
workers.  They run beside the ingest, NOT on the critical path:
``setup.compile_wait_s`` is what they cost it."""

from benchmark.metrics import _oplog


def read(ctx):
    return _oplog.setup_compile_seconds(ctx)
