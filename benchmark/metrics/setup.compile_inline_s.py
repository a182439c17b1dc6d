"""Seconds set-up's operations spent tracing, lowering and compiling (or
reading the persistent cache) on their OWN thread: the programs no worker
compiles — the cut, bin, NaN-scan, sketch and predict programs a ``jit``'s
first call builds inside ``make_device_data``, ``fit_device`` and
``predict``.  On the critical path by construction; the twin of
``setup.compile_wait_s``.  Reads ``trace_s + lower_s + backend_s`` of the
``programs`` entries with ``thread == "own"``."""

from benchmark.metrics import _compile_ledger


def read(ctx):
    return _compile_ledger.total(
        ctx, lambda p: p["trace_s"] + p["lower_s"] + p["backend_s"],
        thread="own")
