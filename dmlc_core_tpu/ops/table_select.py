"""Gather-free per-row lookup of a tiny per-node table.

A TPU gathers an element at a time (5-8 ns each), so the round program
reads ``table[node]`` for every row as a compare-and-sum over the
table's entries: ``sum(where(node[:, None] == iota, table, 0), axis=1)``.
That expression is fast up to 64 entries and 8-16x slower from 128 on,
so a table is looked up in pieces of 64: one piece, the expression
itself, for every table of a tree of depth <= 7.

What happens at the step (TPU v5e; the optimized HLO of both sides, read
in PR 46).  The ``[n, N]`` compare is an intermediate whose LAYOUT the
compiler picks, by padding.  Up to 64 entries it puts the rows on the
128 lanes (64 entries there would be half padding) and the sum runs down
the sublanes: 1.3 ms at 24M rows, what the compares cost.  At 128 or
more the entry axis fills the lanes exactly, goes there, and every row
pays a cross-lane reduce: 21.1 / 19.5 / 21.1 ms at 128 / 256 / 512
entries.  (The forms that lost inside the round program, and why:
PERF.md section 6, PR 46.)  ms a call at 24M rows, int32 and float32
alike (``scripts/sweep_table_select.py``):

    entries         4     16    32    64    128    256    512
    one piece       1.31  1.56  0.90  1.33  21.12  19.49  21.09
    pieces of 64    1.30  1.57  0.89  1.32   3.21   6.28  12.04
"""

import functools
import operator

import jax
import jax.numpy as jnp

__all__ = ["ROW_MAJOR_MAX", "table_select"]

#: entries a piece: the last size at which the compiler keeps the rows
#: of one compare-and-sum on the lanes (the table above)
ROW_MAJOR_MAX = 64


def _row_major(table, node, n_entries):
    n_iota = jnp.arange(n_entries, dtype=jnp.int32)[None, :]
    oh = (node[:, None] == n_iota)
    return jnp.sum(jnp.where(oh, table[None, :], 0), axis=1)


def table_select(table: jax.Array, node: jax.Array,
                 n_entries: int) -> jax.Array:
    """``table[node]`` for every row and ``0`` where ``node`` is outside
    ``0..n_entries-1`` (a padding row's -1), by compare-and-sum over
    pieces of ``ROW_MAJOR_MAX`` entries, piece ``j`` on ``node - 64 j``.

    One entry of one piece is selected per row and everything else is a
    literal zero (a ``where``, not a multiply: a NaN or inf no row
    selects does not leak), so the sums are exact in any order and a
    selected ``-0.0`` comes back ``+0.0``, as from one piece.  Every
    lookup of ``HistGBT``'s round program comes here: ``route`` at each
    level, the ``leaf`` tail, loss-guide growth.
    """
    return functools.reduce(operator.add, (
        _row_major(table[lo:lo + ROW_MAJOR_MAX], node - lo,
                   min(ROW_MAJOR_MAX, n_entries - lo))
        for lo in range(0, n_entries, ROW_MAJOR_MAX)))
