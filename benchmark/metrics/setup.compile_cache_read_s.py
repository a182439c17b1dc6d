"""Seconds set-up spent reading entries of the persistent compile cache,
on every thread: the file's read, its deserialisation and the load onto
the device, which jax reports as one number.  Reads ``read_s`` of the
``programs`` entries (0 on a miss)."""

from benchmark.metrics import _compile_ledger


def read(ctx):
    return _compile_ledger.total(ctx, lambda p: p["read_s"])
