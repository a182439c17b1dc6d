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
    chain           0.35  0.35  0.37  0.64   1.21   5.54   9.63

The last row is :func:`chain_select`, a static chain of selects: one
elementwise pass, no ``[n, N]`` intermediate, no reduce.  It is the
fastest form alone and it is NOT the form of every lookup, for two
reasons that each became a constant here.

* What it is fused INTO.  The tail of ``grow_tree`` (the last descend's
  lookups, then the leaf value's) takes a chain into one fusion with
  every table entry a scalar operand of its own, 361 of them at depth 8,
  and ``leaf`` reads 39 ms for 12.9 (PR 46).  ``route`` (the level
  loop: each row's node's split at every level below the root) hands its
  result straight to the descend over the ``[F, n]`` bins and the chain
  costs what the table says: a level's two or three lookups 2.1-2.7 ms
  as pieces, 0.15-0.27 as chains (ledger, PR 54: ``round.nonhist_ms``
  20.44 -> 11.18 at depth 6, 34.65 -> 23.05 at depth 8, 24M rows).  So
  only ``route`` chains.
* What it costs to TRACE.  Every select of a chain is an equation of the
  round program's jaxpr, traced and lowered again at every warm start
  (the compile cache is keyed on the lowered module): about 11 ms an
  entry on the benchmark's host (ledger, PR 54: 62 / 254 / 381 entries a
  tree cost +1.69 / +3.95 / +5.30 s of ``setup.compile_trace_lower_s``).
  The saving is per ROW, ~2.1 ms a level at 24M rows whatever the
  level's size, and the cost is per ENTRY, doubling every level.  Hence
  :data:`CHAIN_MAX_ENTRIES` and :data:`CHAIN_MIN_ROWS`, and hence ONE
  table a level: ``route`` packs a node's feature, threshold and
  direction into one int32 (:class:`SplitWord`), looks the word up once
  and unpacks it per row with shifts and masks.

:func:`route_form` picks a level's form from those two static shapes.
"""

import functools
import operator
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from dmlc_core_tpu.base.logging import CHECK

__all__ = ["CHAIN_MAX_ENTRIES", "CHAIN_MIN_ROWS", "ROW_MAJOR_MAX",
           "SET_FLAT_MAX", "SET_WORD_BITS", "SplitWord", "chain_select", "route_form",
           "set_words", "set_select", "table_select"]

#: entries a piece: the last size at which the compiler keeps the rows
#: of one compare-and-sum on the lanes (the table above)
ROW_MAJOR_MAX = 64

#: the largest table ``route`` looks up as a chain.  A level's chain
#: saves ~2.1 ms a round at 24M rows whatever its size and costs ~11 ms
#: of warm trace + lower an entry, and the entries double every level:
#: a depth-8 tree's L7 (64 entries) would be half of all its chained
#: entries for 1.8 of 11.8 ms (ledger and traces, PR 54)
CHAIN_MAX_ENTRIES = 32

#: the fewest rows a device at which ``route`` packs and chains.  The
#: saving is per row (24M rows: 9.3-11.6 ms of a 254-613 ms round;
#: 1.18M rows: 1.8 ms of a 1021 ms round, for 5.3 s of set-up; 0.4M and
#: 1.25M rows: nothing — ledger, PR 54) and the cost per entry: below
#: 2^21 rows the level loop traces the unpacked lookups it always has
CHAIN_MIN_ROWS = 1 << 21


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


def chain_select(table: jax.Array, node: jax.Array,
                 n_entries: int) -> jax.Array:
    """:func:`table_select`'s answer for an INTEGER table, bit for bit,
    as a static chain of selects: ``acc = select(node == k, table[k],
    acc)`` for ``k`` in ``0..n_entries-1`` from an ``acc`` of zeros — one
    elementwise pass that fuses into whatever consumes it.  Outside
    ``0..n_entries-1`` no select fires and the zero stays.  Said in
    ``lax``'s own words: every entry is traced again at every warm
    start, and ``jnp.where`` and ``table[k]`` cost twice the tracing for
    the same program (PR 54)."""
    acc = jnp.zeros(node.shape, table.dtype)
    for k in range(n_entries):
        entry = jax.lax.index_in_dim(table, k, keepdims=False)
        acc = jax.lax.select(node == k,
                             jnp.broadcast_to(entry, node.shape), acc)
    return acc


#: bins a word of a node's SET holds (a categorical split sends a row by
#: membership of its bin in its node's set: :func:`set_select`)
SET_WORD_BITS = 32


def set_words(member: jax.Array) -> jax.Array:
    """A set of bins ``member`` [..., n_bins] (bool) as bit words
    [..., ceil(n_bins / 32)] int32: bin ``b`` is bit ``b % 32`` of word
    ``b // 32``."""
    pad = -member.shape[-1] % SET_WORD_BITS
    bits = jnp.pad(member, ((0, 0),) * (member.ndim - 1) + ((0, pad),)
                   ).reshape(member.shape[:-1] + (-1, SET_WORD_BITS))
    weight = jnp.uint32(1) << jnp.arange(SET_WORD_BITS, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(
        jnp.sum(jnp.where(bits, weight, jnp.uint32(0)), axis=-1,
                dtype=jnp.uint32), jnp.int32)


#: the largest set table (words x nodes) looked up as ONE table keyed
#: ``words * node + bin // 32``; past it each word is looked up by the
#: node alone.  ms a lookup at 115M rows, 8 words (PERF.md section 6, PR
#: 58; ``scripts/sweep_cat_route.py``):
#:
#:     nodes            4      16     64     128
#:     one table        8.2    20.2   72.5   141.0
#:     a word at a time 23.0   28.5   64.9   129.1
SET_FLAT_MAX = 2 * ROW_MAJOR_MAX


def set_select(words: jax.Array, node: jax.Array, row_bin: jax.Array,
               n_entries: int) -> jax.Array:
    """Is ``row_bin`` in ``node``'s set?  ``words`` [n_entries, W] are
    the nodes' sets as :func:`set_words` makes them; a bin past the last
    word, and a row whose ``node`` is outside ``0..n_entries-1``, is in
    no set.  Gather-free, in the form the table's size picks: up to
    :data:`SET_FLAT_MAX` words in all ONE :func:`table_select` keyed
    ``W * node + bin // 32``; past it word ``j`` of the row's node for
    each of the W words (W lookups of ``n_entries``), the row's own
    picked by a chain of W selects.  Then the bit, by a shift.  The
    per-row cost of a categorical split: ``W x n_entries`` select-adds a
    row a level, where a threshold's packed word costs ``n_entries``."""
    n_words = words.shape[1]
    at = row_bin >> 5
    if n_entries * n_words <= SET_FLAT_MAX:
        # (a pad row's -1 keys below 0 by itself)
        key = jnp.where(at < n_words, node * n_words + at, -1)
        word = table_select(words.reshape(-1), key, n_entries * n_words)
    else:
        word = jnp.zeros(node.shape, jnp.int32)
        for j in range(n_words):
            word = jnp.where(
                at == j, table_select(words[:, j], node, n_entries), word)
    return ((word >> (row_bin & 31)) & 1) == 1


def route_form(n_prev: int, rows_per_device: int) -> str:
    """How ``route`` reads each row's split at a level whose parents
    number ``n_prev``, from the two shapes the program can see:

    * ``"tables"`` below :data:`CHAIN_MIN_ROWS` rows a device: feature,
      threshold and direction each through :func:`table_select`;
    * ``"chain"``: ONE :class:`SplitWord` a node through
      :func:`chain_select`, up to :data:`CHAIN_MAX_ENTRIES` parents;
    * ``"pieces"``: the same word through :func:`table_select` past
      them (one compare-and-sum where there were two or three)."""
    if rows_per_device < CHAIN_MIN_ROWS:
        return "tables"
    return "chain" if n_prev <= CHAIN_MAX_ENTRIES else "pieces"


class SplitWord(NamedTuple):
    """Where a node's split sits in one non-negative int32:
    ``(feat << (thr_bits + dir_bits)) | (thr << dir_bits) | dir``."""
    thr_bits: int
    dir_bits: int

    @classmethod
    def of(cls, n_features: int, n_bins: int, missing: bool) -> "SplitWord":
        """The layout for features ``0..n_features-1``, thresholds
        ``0..n_bins-1`` and, under ``missing``, a direction bit."""
        word = cls((n_bins - 1).bit_length(), int(missing))
        bits = (n_features - 1).bit_length() + word.thr_bits + word.dir_bits
        CHECK(bits <= 31,
              f"a split of {n_features} features x {n_bins} bins"
              f"{' and a direction' if missing else ''} takes {bits} bits:"
              f" one int32 word holds 31")
        return word

    def pack(self, feat: jax.Array, thr: jax.Array,
             dirv: Optional[jax.Array] = None) -> jax.Array:
        word = ((feat << (self.thr_bits + self.dir_bits))
                | (thr << self.dir_bits))
        return word | dirv if self.dir_bits else word

    def unpack(self, word: jax.Array
               ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
        """``(feat, thr, dir)`` of a packed word, ``dir`` ``None`` where
        the word has no direction bit; word 0 (a padding row's) is all
        zeros."""
        feat = word >> (self.thr_bits + self.dir_bits)
        thr = (word >> self.dir_bits) & ((1 << self.thr_bits) - 1)
        return feat, thr, (word & 1) if self.dir_bits else None
