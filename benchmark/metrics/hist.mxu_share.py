"""MXU flops the histogram kernels need (``costs.py``, per chip, from the
shapes and ``round_plan``) over the device time those kernels took, as a
share of the chip's bf16 peak."""

from benchmark import costs, peaks
from benchmark.metrics import _names


def read(ctx):
    s, cfg = ctx.summary, ctx.config
    t = s.op_seconds(_names.is_hist_kernel)
    plan = ctx.counters.get("round_plan")
    if not t or plan is None:
        return None
    flops = costs.hist_mxu_flops_per_round(
        int(cfg["rows"]) // ctx.chips, int(cfg["features"]),
        int(cfg["n_bins"]), int(cfg["max_depth"]), plan)
    rounds = sum(ctx.op_work)
    return 100.0 * flops * rounds / t / peaks.peak(
        ctx.device_kind)["bf16_flops"]
