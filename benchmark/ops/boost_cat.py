"""Operation ``boost_cat``: one whole ``fit_device`` on a device-resident
handle of a table whose columns are partly NAMES — ``ops/boost.py``'s
window (its ``op`` is that file's, line for line) with the categorical
parameters of the configuration passed through ``system_cat.new_model``,
the rows drawn by ``datagen_cat`` and a check that knows sets
(``checks_cat``).

Set-up makes the model FIRST: a program that does not know the three
hyperparameters refuses them there, before any row is drawn.  Then it
stages the configuration's rows through ``make_device_data`` and runs one
warm fit of the window's own rounds, so the window compiles nothing.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import checks, checks_cat, system, system_cat


def setup(ctx) -> None:
    p = ctx.params
    model = system_cat.new_model(ctx, p["n_trees"])
    X, y = system_cat.training_rows(ctx)
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
    """The operation's record: the share of the last fit's splits that
    are on categorical columns (every one of them scanned, and routed at
    every level, through the set path)."""
    if not ctx.state["last"]:
        return
    types = ctx.config["feature_types"]
    n_bins = int(ctx.config["n_bins"])
    split = cat = 0
    for t in system.host_trees(ctx.state["last"]):
        for level in range(t["feat"].shape[0]):
            real = t["thr"][level, :1 << level] < n_bins - 1
            split += int(real.sum())
            cat += sum(types[f] == "c"
                       for f in t["feat"][level, :1 << level][real])
    ctx.counters["cat.split_share"] = cat / max(split, 1)
    ctx.say(f"[bench] {split} splits in {len(ctx.state['last'])} trees, "
            f"{cat} of them ({100.0 * cat / max(split, 1):.1f}%) on "
            f"categorical columns")


def check(ctx) -> None:
    st, cfg, p = ctx.state, ctx.config, ctx.params
    model, X, y = st["model"], st["X"], st["y"]
    n = len(y)
    types, n_bins = cfg["feature_types"], int(cfg["n_bins"])
    cuts = np.asarray(model.cuts)
    t0 = time.perf_counter()
    bins_t = np.asarray(st["handle"]["bins_t"])[:, :n]
    ctx.say(f"[bench] binned matrix fetched in "
            f"{time.perf_counter() - t0:.3f} s")
    last = system.host_trees(st["last"])
    numbers = {"rounds_share": len(last) / float(p["n_trees"])}
    # the binned matrix the trees were grown on is the raw rows' own: the
    # tables against the reference's from ALL rows, then a block of rows
    # against the program's tables and cuts
    rng = np.random.default_rng(ctx.seed)
    k = min(int(p["check_bin_rows"]), n)
    lo = int(rng.integers(0, n - k + 1))
    t0 = time.perf_counter()
    numbers["bins_mismatches"] = (
        checks_cat.tables_mismatches(
            {f: X[:, f] for f, t in enumerate(types) if t == "c"}, cuts,
            n_bins)
        + checks_cat.bins_mismatches(X[lo:lo + k], bins_t[:, lo:lo + k],
                                     cuts, types))
    ctx.say(f"[bench] tables and bins compared in "
            f"{time.perf_counter() - t0:.3f} s")
    if len(last) < 2:
        checks.apply_limits(ctx, numbers)      # nothing was boosted
        return
    t0 = time.perf_counter()
    tree_numbers, facts = checks_cat.tree_numbers(bins_t, y, last, cuts, cfg)
    ctx.say(f"[bench] trees 0 and 1 replayed in "
            f"{time.perf_counter() - t0:.3f} s: {facts}")
    numbers.update(tree_numbers)
    ctx.counters.update({"cat." + k: v for k, v in facts.items()})
    # two fits of one handle give byte-identical trees: the window's first
    # and last operation, and the warm fit against the rounds it shares
    numbers["ops_trees_differ"] = (
        checks.trees_differ(st["first"], st["last"])
        + checks.trees_differ(st["warm"], st["last"][:len(st["warm"])]))
    Xh, yh = system_cat.heldout_rows(ctx, int(p["check_heldout_rows"]))
    m = min(int(p["check_train_rows"]), n)
    numbers.update(checks_cat.learning_numbers(
        X[:m], y[:m], yh, model.predict(Xh), cuts, last, cfg))
    checks.apply_limits(ctx, numbers)
