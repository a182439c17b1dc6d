"""What one boosting round costs, counted from shapes and from what
``model.round_plan`` says the program runs.  A copy of the arithmetic of
``bench.py::_derived_metrics`` (listed in PERF.md for deletion there),
kept with the benchmark so that a PR which claims a gain cannot move it.

MXU flops of the histogram kernels, per chip and per round: at level l
the kernel multiplies a one-hot matrix ``[A, T]`` by ``[T, lo]`` for every
feature over all of the chip's rows, ``A = 2 * n_build * hi`` with
``hi = ceil(n_bins / lo)``; sibling subtraction builds the root and then
only left children, ``n_build = 1, 1, 2, 4, ...``.  ``A * lo`` does not
depend on how the kernel factors the bins (``hi * lo >= n_bins`` and is
``n_bins`` for every power-of-two factor of 256), so the count needs no
table of the kernel's tuning: ``2 * (2 * n_build * n_bins) * rows * F``.
The fused and the staged round kernels do the same MXU work and differ
only in bytes.
"""

from __future__ import annotations

from typing import Any, Dict


def levels_built(max_depth: int):
    """``n_build`` of each depth-wise level."""
    return [1 if lv == 0 else 1 << (lv - 1) for lv in range(max_depth)]


def hist_mxu_flops_per_round(rows_per_chip: int, features: int, n_bins: int,
                             max_depth: int, round_plan: Dict[str, Any]
                             ) -> float:
    if round_plan.get("grow_policy", "depthwise") != "depthwise":
        raise ValueError("only the depth-wise round is counted here; add "
                         "the loss-guide count before benchmarking it")
    if round_plan.get("bin_layout") is not None:
        raise ValueError("a packed or bundled bin layout changes the "
                         "kernel's width; count it before benchmarking it")
    return float(sum(2 * (2 * nb * n_bins) * rows_per_chip * features
                     for nb in levels_built(max_depth)))


def bins_bytes_per_round(rows_per_chip: int, features: int, max_depth: int,
                         round_plan: Dict[str, Any]) -> float:
    """Bytes of the uint8 bin matrix one round reads from HBM: one pass
    per level when the round kernel is fused (the descend rides the
    histogram's read), ``2 * depth - 1`` passes when staged."""
    passes = max_depth if round_plan.get("fused_round") else 2 * max_depth - 1
    return float(passes * rows_per_chip * features)
