"""Operation ``boost_lossguide``: one whole ``fit_device`` of LEAF-WISE
trees on a device-resident handle — ``ops/boost.py``'s window (its ``op``
is that file's, line for line) with the growth policy and the leaf budget
of the configuration passed through ``system_lossguide.new_model`` and a
check that knows node lists (``checks_lossguide``).

Set-up makes the model FIRST: a program that does not know the two
hyperparameters refuses them there, before any row is made.  Then it
stages the configuration's rows through ``make_device_data`` and runs one
warm fit of the window's own rounds, so the window compiles nothing.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import (checks, checks_lossguide, reference_lossguide as rl,
                       system, system_lossguide)


def setup(ctx) -> None:
    p = ctx.params
    model = system_lossguide.new_model(ctx, p["n_trees"])
    X, y = system.training_rows(ctx)
    handle = system.ingest(model, X, y)
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


def finish(ctx) -> None:
    """The operation's record: what the last fit's first tree looks like."""
    if not ctx.state["last"]:
        return
    tree = system.host_trees(ctx.state["last"][:1])[0]
    ctx.counters["tree0.depth"] = rl.depth_of(tree)
    ctx.counters["tree0.leaves"] = len(rl.leaves_of(tree))
    ctx.say(f"[bench] tree 0: {ctx.counters['tree0.leaves']} leaves, depth "
            f"{ctx.counters['tree0.depth']}, node list of "
            f"{len(tree['left'])}")


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
    t0 = time.perf_counter()
    tree_numbers, facts = checks_lossguide.tree_numbers(bins_t, y, last, cfg)
    ctx.say(f"[bench] trees 0 and 1 replayed in "
            f"{time.perf_counter() - t0:.3f} s: {facts}")
    numbers.update(tree_numbers)
    # what the readers of a traced run count from (costs_lossguide)
    ctx.counters.update({"lossguide." + k: v for k, v in facts.items()})
    # two fits of one handle give byte-identical trees: the window's first
    # and last operation, and the warm fit against the rounds it shares
    numbers["ops_trees_differ"] = (
        checks.trees_differ(st["first"], st["last"])
        + checks.trees_differ(st["warm"], st["last"][:len(st["warm"])]))
    Xh, yh = system.heldout_rows(ctx, int(p["check_heldout_rows"]))
    m = min(int(p["check_train_rows"]), n)
    numbers.update(checks_lossguide.learning_numbers(
        X[:m], y[:m], Xh, yh, cuts, last, cfg))
    checks.apply_limits(ctx, numbers)
