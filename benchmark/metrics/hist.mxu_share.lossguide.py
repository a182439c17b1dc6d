"""MXU flops the histogram builds of a leaf-wise round NEED
(``costs_lossguide.py``: the root over all rows, each expansion over its
smaller child's, from the check's float64 replay of the first tree) over
the device time the histogram kernels took, as a share of the chip's bf16
peak.  It reads the same work whatever builds it: full masked passes read
a few percent, builds over a leaf's own rows would read what the kernel
reaches.  Nothing without the replay's counts (a depth-wise cell, a run
whose check did not get there)."""

from benchmark import costs_lossguide, peaks
from benchmark.metrics import _names


def read(ctx):
    s, cfg = ctx.summary, ctx.config
    t = s.op_seconds(_names.is_hist_kernel)
    needed = ctx.counters.get("lossguide.needed_rows")
    if not t or not needed:
        return None
    flops = costs_lossguide.hist_mxu_flops_per_tree(
        int(needed) // ctx.chips, int(cfg["features"]), int(cfg["n_bins"]))
    trees = sum(ctx.op_work)
    return 100.0 * flops * trees / t / peaks.peak(
        ctx.device_kind)["bf16_flops"]
