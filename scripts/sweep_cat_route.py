#!/usr/bin/env python3
"""How a row finds out whether its bin is in its node's set: the forms
tried, timed on the chip at the categorical cell's rows (PERF.md section
6, PR 58).  ``chiprun -- env ROWS=115000000 python scripts/sweep_cat_route.py``

Per level size N (the parents whose sets are looked up) and form, the
milliseconds of one lookup over ROWS rows, best of three:

* ``words``: ``ops.table_select.set_select`` — the shipped form: 8 bit
  words a node, word j of the row's node by ``table_select`` for each j
  (the compares shared), the row's own by 8 selects, the bit by a shift;
* ``flat``: ONE ``table_select`` over ``8 N`` entries keyed ``8 node +
  bin // 32``;
* ``rank``: a ``[N, 256]`` table of ranks (or of 0/1) keyed ``256 node +
  bin`` through ``table_select`` — "rank the bins and keep <=";
* ``mxu``: the nodes' sets as 16-bit halves in float32 ``[16, N]`` times
  the one-hot of the node ``[N, rows]`` on the MXU (precision HIGHEST:
  exact), in row chunks, then the half picked by 16 selects;
* ``thr``: what a numeric level pays for comparison: ONE ``table_select``
  of N entries and a compare.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from dmlc_core_tpu.ops.table_select import (set_select, set_words,  # noqa: E402
                                            table_select)

ROWS = int(os.environ.get("ROWS", 115_000_000))
LEVELS = [int(v) for v in os.environ.get("LEVELS", "4,16,64,128").split(",")]
CHUNK = 1 << 20


def words_form(words, node, row_bin, n):
    return set_select(words, node, row_bin, n)


def flat_form(words, node, row_bin, n):
    word = table_select(words.reshape(-1), node * 8 + (row_bin >> 5), 8 * n)
    return ((word >> (row_bin & 31)) & 1) == 1


def rank_form(member, node, row_bin, n):
    return table_select(member.reshape(-1), node * 256 + row_bin,
                        256 * n) == 1


def mxu_form(halves_t, node, row_bin, n):
    pad = -node.shape[0] % CHUNK
    nd = jnp.pad(node, (0, pad), constant_values=-1).reshape(-1, CHUNK)
    rb = jnp.pad(row_bin, (0, pad)).reshape(-1, CHUNK)

    def chunk(args):
        nd_c, rb_c = args
        onehot = (jnp.arange(n, dtype=jnp.int32)[:, None]
                  == nd_c[None, :]).astype(jnp.float32)
        got = jnp.dot(halves_t, onehot,
                      precision=jax.lax.Precision.HIGHEST)     # [16, chunk]
        at = rb_c >> 4
        half = jnp.sum(jnp.where(
            jnp.arange(16, dtype=jnp.int32)[:, None] == at[None, :], got, 0.0),
            axis=0).astype(jnp.int32)
        return ((half >> (rb_c & 15)) & 1) == 1

    return jax.lax.map(chunk, (nd, rb)).reshape(-1)[:node.shape[0]]


def thr_form(thr, node, row_bin, n):
    return row_bin > table_select(thr, node, n)


#: each form compiled once a shape (``n`` is static)
JITTED = {name: jax.jit(fn, static_argnums=(3,)) for name, fn in (
    ("words", words_form), ("flat", flat_form), ("rank", rank_form),
    ("mxu", mxu_form), ("thr", thr_form))}


def timed(fn, *args):
    fn(*args).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - t)
    return best * 1e3


def main():
    rng = np.random.default_rng(0)
    out = {"rows": ROWS, "device": jax.devices()[0].device_kind, "ms": {}}
    row_bin = jnp.asarray(rng.integers(0, 256, ROWS, dtype=np.int32))
    for n in LEVELS:
        node = jnp.asarray(rng.integers(0, n, ROWS, dtype=np.int32))
        member = rng.random((n, 256)) < 0.25
        words = set_words(jnp.asarray(member))
        want = None
        got = {}
        tables = {"words": words, "flat": words,
                  "thr": jnp.asarray(rng.integers(0, 256, n, dtype=np.int32))}
        if n <= 16:
            tables["rank"] = jnp.asarray(member.astype(np.int32))
        halves = (member.reshape(n, 16, 16)
                  * (1 << np.arange(16))).sum(-1).astype(np.float32)
        tables["mxu"] = jnp.asarray(halves.T)
        for name, table in tables.items():
            jitted = JITTED[name]
            try:
                ms = timed(jitted, table, node, row_bin, n)
            except Exception as e:  # noqa: BLE001 - a form the chip refuses
                got[name] = f"{type(e).__name__}: {str(e)[:200]}"
                continue
            got[name] = ms
            if name != "thr":
                res = np.asarray(jitted(table, node, row_bin, n)[:100000])
                if want is None:
                    want = res
                elif not np.array_equal(res, want):
                    got[name] = f"WRONG ({ms:.2f} ms)"
        out["ms"][str(n)] = got
        print(json.dumps({n: got}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/sweep_cat_route.json", "w") as f:
        json.dump(out, f)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
