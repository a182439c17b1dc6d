"""Sweep the gather-free table lookup of the round program by form.

``table_select(table, node, n_entries)`` (ops/table_select.py) reads a
tiny per-node table for every row as a compare-and-sum over 64-entry
pieces.  This sweep times it, the ONE compare-and-sum it is made of
(``row_major``: the same thing up to 64 entries, the cliff past them)
and the shipped ``chain_select`` (a static chain of selects: what
``route`` takes for a small table under many rows, integers only) alone,
jitted, per (rows, n_entries, dtype), checks each bit for bit against
plain ``table[node]`` indexing, and prints the table the piece size
``ROW_MAJOR_MAX`` quotes.  The ``packed`` rows are ``route``'s level as
it is traced in the HIGGS cells: feature and threshold of a node in ONE
int32 (``SplitWord``), looked up once and unpacked per row, against the
two separate lookups it replaces — as chains (``packed chain | 2
chain``) and as pieces (``packed pieces | 2 pieces``).  ``HLO=dir`` also
writes each case's optimized HLO there (layout of the compare, which
axis sits on the lanes, the fusion's window).  The forms tried and
dropped (``[N, n]`` summed over axis 0, two-stage, a two-level chain)
are in PERF.md section 6, PR 46.

Usage: ``ROWS=24000000 python scripts/sweep_table_select.py``
(``ROWS=24000000,3771125,400000 ENTRIES=4,16,32,64,128,256,512``; the
chain traces an equation an entry: keep it to 64 entries or fewer).
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

from dmlc_core_tpu.ops.table_select import SplitWord
from dmlc_core_tpu.ops.table_select import _row_major as row_major
from dmlc_core_tpu.ops.table_select import chain_select as chain
from dmlc_core_tpu.ops.table_select import table_select as pieces

ROWS = [int(x) for x in os.environ.get("ROWS", "24000000").split(",")]
ENTRIES = [int(x) for x in
           os.environ.get("ENTRIES", "16,32,64,128,256,512").split(",")]
DTYPES = ("int32", "float32")
REPEATS, CALLS = 3, 10
HLO = os.environ.get("HLO")


#: the chain is the shipped function and takes integers only (a selected
#: ``-0.0`` would come back as it is); inside the round program the
#: compiler fuses it into its consumers, every table entry a scalar
#: operand of its own: right for ``route``, 38 ms for the tail's three
#: lookups (PERF.md section 6, PRs 46 and 55)
FORMS = {"row_major": row_major, "pieces": pieces, "chain": chain}
WORD = SplitWord.of(28, 256, False)


def packed(lookup, feat, thr, node, n_entries):
    """``route``'s level: one word a node, one lookup, unpacked per row
    (summed so that both fields are computed)."""
    f, t, _ = WORD.unpack(lookup(WORD.pack(feat, thr), node, n_entries))
    return f + t


def unpacked(lookup, feat, thr, node, n_entries):
    return lookup(feat, node, n_entries) + lookup(thr, node, n_entries)


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
                if name == "chain" and dtype != "int32":
                    continue
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
        # route's level: ONE packed word against the two tables
        feat, thr = (jnp.asarray(rng.integers(0, hi, n_entries)
                                 .astype(np.int32)) for hi in (28, 256))
        line = []
        for name in ("chain", "pieces"):
            one = jax.jit(partial(packed, FORMS[name], n_entries=n_entries))
            two = jax.jit(partial(unpacked, FORMS[name],
                                  n_entries=n_entries))
            exact = bool(np.array_equal(np.asarray(one(feat, thr, node)),
                                        np.asarray(two(feat, thr, node))))
            ms = [time_ms(fn, feat, thr, node) for fn in (one, two)]
            results[f"{rows}/{n_entries}/packed/{name}"] = {
                "ms": round(ms[0], 4), "unpacked_ms": round(ms[1], 4),
                "exact": exact}
            line.append(f"packed {name} {ms[0]:8.3f} | 2 {name} {ms[1]:8.3f}"
                        f"{'' if exact else ' WRONG'}")
        print(f"rows={rows:9d} N={n_entries:4d} {'packed':8s} ms: "
              + " | ".join(line), flush=True)
print(json.dumps(results))
