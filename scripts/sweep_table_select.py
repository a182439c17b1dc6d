"""Sweep the gather-free table lookup of the round program by form.

``table_select(table, node, n_entries)`` (ops/table_select.py) reads a
tiny per-node table for every row as a compare-and-sum over 64-entry
pieces.  This sweep times it, the ONE compare-and-sum it is made of
(``row_major``: the same thing up to 64 entries, the cliff past them)
and a static chain of selects (ROADMAP S9's lever for ``route``) alone,
jitted, per (rows, n_entries, dtype), checks each bit for bit against
plain ``table[node]`` indexing, and prints the table the piece size
``ROW_MAJOR_MAX`` quotes.  ``HLO=dir`` also writes each case's optimized
HLO there (layout of the compare, which axis sits on the lanes, the
fusion's window).  The forms tried and dropped (``[N, n]`` summed over
axis 0, two-stage, a two-level chain) are in PERF.md section 6, PR 46.

Usage: ``ROWS=24000000 python scripts/sweep_table_select.py``
(``ROWS=24000000,3771125,400000 ENTRIES=4,16,32,64,128,256,512``).
"""
import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from dmlc_core_tpu.ops.table_select import _row_major as row_major
from dmlc_core_tpu.ops.table_select import table_select as pieces

ROWS = [int(x) for x in os.environ.get("ROWS", "24000000").split(",")]
ENTRIES = [int(x) for x in
           os.environ.get("ENTRIES", "16,32,64,128,256,512").split(",")]
DTYPES = ("int32", "float32")
REPEATS, CALLS = 3, 10
HLO = os.environ.get("HLO")


def chain(table, node, n_entries):
    """No reduce: a static chain of selects, one elementwise pass.  The
    fastest form alone; inside the round program the compiler fuses it
    into its consumers, every table entry a scalar operand of its own,
    and the tail's three lookups cost 38 ms (PERF.md section 6, PR 46)."""
    table = jnp.where(table == 0, jnp.zeros((), table.dtype), table)
    acc = jnp.zeros(node.shape, table.dtype)
    for k in range(n_entries):
        acc = jnp.where(node == k, table[k], acc)
    return acc


FORMS = {"row_major": row_major, "pieces": pieces, "chain": chain}


def time_ms(fn, *args):
    best = None
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        for _ in range(CALLS):
            out = fn(*args)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / CALLS
        best = dt if best is None else min(best, dt)
    return best * 1e3


if HLO:
    os.makedirs(HLO, exist_ok=True)
dev = jax.devices()[0]
print(f"device {dev.platform} {dev.device_kind}", flush=True)
rng = np.random.default_rng(0)
results = {}
for rows in ROWS:
    for n_entries in ENTRIES:
        node_np = rng.integers(0, n_entries, rows).astype(np.int32)
        node_np[rng.integers(0, rows, max(rows // 100, 1))] = -1
        for dtype in DTYPES:
            table_np = (rng.normal(size=n_entries).astype(np.float32)
                        if dtype == "float32" else
                        rng.integers(0, 256, n_entries).astype(np.int32))
            if dtype == "float32" and n_entries > 1:
                # a selected -0.0 reads +0.0 from every form; an entry
                # no row selects may hold anything
                table_np[0] = -0.0
                node_np = np.where(node_np == 1, 0, node_np)
                table_np[1] = np.nan
            want = np.where(node_np >= 0, table_np[node_np], 0) + 0
            table, node = jnp.asarray(table_np), jnp.asarray(node_np)
            line = []
            for name in FORMS:
                fn = jax.jit(partial(FORMS[name], n_entries=n_entries))
                got = np.asarray(fn(table, node))
                exact = bool(np.array_equal(got.view(np.uint32),
                                            want.view(np.uint32)))
                ms = time_ms(fn, table, node)
                results[f"{rows}/{n_entries}/{dtype}/{name}"] = {
                    "ms": round(ms, 4), "exact": exact}
                line.append(f"{name} {ms:8.3f}{'' if exact else ' WRONG'}")
                if HLO:
                    path = os.path.join(
                        HLO, f"{name}_{rows}_{n_entries}_{dtype}.txt")
                    with open(path, "w") as f:
                        f.write(fn.lower(table, node).compile().as_text())
            print(f"rows={rows:9d} N={n_entries:4d} {dtype:8s} ms: "
                  + " | ".join(line), flush=True)
print(json.dumps(results))
