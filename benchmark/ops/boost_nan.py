"""Operation ``boost_nan``: operation ``boost`` on a table with holes —
one whole ``fit_device`` on a device-resident handle whose rows are mostly
NaN, so every round scores each threshold with the missing mass on either
side, keeps the better direction and routes by it.

The window is ``ops/boost.py``'s, word for word: set-up stages the
configuration's rows through ``make_device_data`` (all of them, no
``cuts=``) and runs one warm fit of ``warm_trees`` rounds, the same
program the window dispatches; every operation boosts ``n_trees`` rounds
into a fresh ensemble and counts ``n_trees`` rounds of work; its wall
runs from entering ``fit_device`` to the last chunk's trees on the host.
The rows are ``datagen_missing``'s and the check is against
``reference_missing``; the system is reached through ``system.py`` alone.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import checks, checks_missing, datagen_missing, system


def setup(ctx) -> None:
    p = ctx.params
    X, y = datagen_missing.bosch_like(int(ctx.config["rows"]),
                                      int(ctx.config["features"]),
                                      ctx.seed, stream=0)
    model = system.new_model(ctx, p["n_trees"])
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
    # the binned matrix the trees were grown on is the raw rows' own,
    # every NaN in the reserved bin and nothing else there
    rng = np.random.default_rng(ctx.seed)
    k = min(int(p["check_bin_rows"]), n)
    lo = int(rng.integers(0, n - k + 1))
    numbers.update(checks_missing.bin_numbers(
        X[lo:lo + k], bins_t[:, lo:lo + k], cuts, cfg))
    if len(last) < 2:
        checks.apply_limits(ctx, numbers)      # nothing was boosted
        return
    numbers.update(checks_missing.boost_tree_numbers(bins_t, y, last, cfg))
    # two fits of one handle give byte-identical trees: the window's first
    # and last operation, and the warm fit against the rounds it shares
    numbers["ops_trees_differ"] = (
        checks.trees_differ(st["first"], st["last"])
        + checks.trees_differ(st["warm"], st["last"][:len(st["warm"])]))
    Xh, yh = datagen_missing.bosch_like(int(p["check_heldout_rows"]),
                                        int(cfg["features"]), ctx.seed,
                                        stream=1)
    m = min(int(p["check_train_rows"]), n)
    numbers.update(checks_missing.learning_numbers(X[:m], y[:m], Xh, yh,
                                                   cuts, last, cfg))
    checks.apply_limits(ctx, numbers)
