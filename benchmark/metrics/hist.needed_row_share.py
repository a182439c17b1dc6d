"""Share of the rows the histogram kernels of a leaf-wise tree were handed
that belonged to the node they built: the rows the builds NEED
(``costs_lossguide.py``, from the check's replay of the first tree) over
``round_plan["hist_rows_per_build"]`` x the tree's builds.  Nothing
without the replay's counts or the plan's key."""

from benchmark import costs_lossguide


def read(ctx):
    plan = ctx.counters.get("round_plan") or {}
    needed = ctx.counters.get("lossguide.needed_rows")
    builds = ctx.counters.get("lossguide.builds")
    if not needed or not builds or not plan.get("hist_rows_per_build"):
        return None
    return 100.0 * costs_lossguide.needed_row_share(
        int(needed), int(plan["hist_rows_per_build"]) * ctx.chips,
        int(builds))
