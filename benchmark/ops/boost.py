"""Operation ``boost``: one whole ``fit_device`` on a device-resident handle.

Set-up stages the configuration's rows through ``make_device_data`` (all
of them, no ``cuts=``) and runs one short warm fit: ``warm_trees`` rounds
compile and run the same 25-round program the window dispatches, so the
window compiles nothing.  Every operation of the window boosts
``n_trees`` rounds into a fresh ensemble and counts ``n_trees`` rounds of
work; its wall runs from entering ``fit_device`` to the last chunk's
trees on the host.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import checks, system


def setup(ctx) -> None:
    p = ctx.params
    X, y = system.training_rows(ctx)
    model = system.new_model(ctx, p["n_trees"])
    handle = system.ingest(model, X, y)
    # a user's repeated fit with fewer rounds: same program, a quarter of
    # the set-up
    model.param.n_trees = int(p["warm_trees"])
    model.fit_device(handle)
    model.param.n_trees = int(p["n_trees"])
    ctx.state.update(X=X, y=y, model=model, handle=handle,
                     warm=model.trees, first=None, last=None)


def op(ctx, i: int) -> float:
    model = ctx.state["model"]
    model.fit_device(ctx.state["handle"])
    if ctx.state["first"] is None:
        ctx.state["first"] = model.trees
    ctx.state["last"] = model.trees
    ctx.counters["round_plan"] = model.round_plan
    return float(len(model.trees))


def check(ctx) -> None:
    st, cfg, p = ctx.state, ctx.config, ctx.params
    model, X, y = st["model"], st["X"], st["y"]
    n = len(y)
    cuts = np.asarray(model.cuts)
    t0 = time.perf_counter()
    bins_t = np.asarray(st["handle"]["bins_t"])[:, :n]
    ctx.say(f"[bench] binned matrix fetched in "
            f"{time.perf_counter() - t0:.3f} s")
    last = system.host_trees(st["last"])
    numbers = {"rounds_share": len(last) / float(p["n_trees"])}
    # the binned matrix the trees were grown on is the raw rows' own
    rng = np.random.default_rng(ctx.seed)
    k = min(int(p["check_bin_rows"]), n)
    lo = int(rng.integers(0, n - k + 1))
    numbers["bins_mismatches"] = checks.bins_mismatches(
        X[lo:lo + k], bins_t[:, lo:lo + k], cuts)
    if len(last) < 2:
        checks.apply_limits(ctx, numbers)      # nothing was boosted
        return
    numbers.update(checks.boost_tree_numbers(bins_t, y, last, cfg))
    # two fits of one handle give byte-identical trees: the window's first
    # and last operation, and the warm fit against the rounds it shares
    numbers["ops_trees_differ"] = (
        checks.trees_differ(st["first"], st["last"])
        + checks.trees_differ(st["warm"], st["last"][:len(st["warm"])]))
    Xh, yh = system.heldout_rows(ctx, int(p["check_heldout_rows"]))
    m = min(int(p["check_train_rows"]), n)
    numbers.update(checks.learning_numbers(X[:m], y[:m], Xh, yh, cuts, last,
                                           cfg))
    checks.apply_limits(ctx, numbers)
