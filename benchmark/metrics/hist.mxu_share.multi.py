"""``hist.mxu_share`` where a round grows several trees: the MXU flops
ONE tree's histogram kernels need (``costs.py``, from the shapes and
``round_plan``) times the program's own ``round_plan["trees_per_round"]``
times the rounds, over the device time the Mosaic calls took, as a share
of the chip's bf16 peak — the kernel's share of its roofline in a cell of
several classes.  (``costs.hist_mxu_flops_per_round`` counts one tree a
round, so ``hist.mxu_share`` itself would read a K-th there: PERF.md
section 7.)  Nothing on a program whose plan has no ``trees_per_round``."""

from benchmark import costs, peaks
from benchmark.metrics import _names


def read(ctx):
    s, cfg = ctx.summary, ctx.config
    t = s.op_seconds(_names.is_hist_kernel)
    plan = ctx.counters.get("round_plan")
    if not t or plan is None or "trees_per_round" not in plan:
        return None
    flops = costs.hist_mxu_flops_per_round(
        int(cfg["rows"]) // ctx.chips, int(cfg["features"]),
        int(cfg["n_bins"]), int(cfg["max_depth"]), plan)
    rounds = sum(ctx.op_work)
    return (100.0 * flops * int(plan["trees_per_round"]) * rounds / t
            / peaks.peak(ctx.device_kind)["bf16_flops"])
