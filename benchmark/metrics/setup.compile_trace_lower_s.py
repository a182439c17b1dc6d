"""Seconds set-up spent turning Python into a jaxpr and the jaxpr into
MLIR, on every thread: paid hit or miss, before the persistent cache can
be asked.  Reads ``trace_s + lower_s`` of the ``programs`` entries."""

from benchmark.metrics import _compile_ledger


def read(ctx):
    return _compile_ledger.total(ctx, lambda p: p["trace_s"] + p["lower_s"])
