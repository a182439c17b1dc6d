"""Seconds of set-up's backend phases outside the cache's read, on every
thread: on a warm run the price of finding the key (serialising and
hashing the module), on a cold one XLA itself and the entry's write.
Reads ``backend_s - read_s`` of the ``programs`` entries."""

from benchmark.metrics import _compile_ledger


def read(ctx):
    return _compile_ledger.total(ctx, lambda p: p["backend_s"] - p["read_s"])
