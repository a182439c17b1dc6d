"""What the histogram builds of a LEAF-WISE round have to do, counted so
that it reads the same work whatever implements it (``costs.py`` counts
the depth-wise round and refuses this plan).

A tree of L leaves needs the root's histogram over all n rows and, for
each of its L - 1 expansions, ONE child's — the other is the parent less
that one — and the cheaper child to build is the smaller.  So the rows
the builds NEED are ``n + sum over expansions of min(rows_left,
rows_right)``, whatever the program hands its kernels (today every build
is handed all n rows, the other leaves masked out:
``round_plan["hist_rows_per_build"]``).  Per row the kernel multiplies a
one-hot ``[2 * n_bins]`` (gradient and hessian planes of one node) into
every feature: ``2 * (2 * n_bins) * F`` flops, ``costs.py``'s count at
``n_build = 1``.  The row counts come from the check's own float64 replay
of the timed fit's first tree (``checks_lossguide.tree_numbers``'s
``facts``); the other trees of a fit are counted as that one.
"""

from __future__ import annotations


def hist_mxu_flops_per_tree(needed_rows: int, features: int, n_bins: int
                            ) -> float:
    return float(2 * (2 * n_bins) * features * needed_rows)


def needed_row_share(needed_rows: int, hist_rows_per_build: int, builds: int
                     ) -> float:
    """Rows the builds need over rows the kernels were handed."""
    return needed_rows / float(hist_rows_per_build * builds)
