"""Operation ``boost_paged``: operation ``boost`` on a handle that was
staged from pages — one whole ``fit_device`` on the device-resident bins
of a one-hot table that reached the chip through ``DiskRowIter`` pages,
the streaming sketch and ``make_device_data_iter``.

The window is ``ops/boost.py``'s, word for word: every operation boosts
``n_trees`` rounds into a fresh ensemble and counts ``n_trees`` rounds of
work; its wall runs from entering ``fit_device`` to the last chunk's
trees on the host.  Set-up builds the model first (a program that cannot
run the cell fails before a row is drawn), stages the rows ONCE through
``ops/ingest_paged.py``'s own staging and runs one warm fit of
``warm_trees`` rounds — the program the window dispatches.  The rows are
``datagen_onehot``'s; the staging's sketched cuts are judged by their
rank in the exactly sorted column (``reference_paged``), the trees by
``reference.py``'s grower over the program's own bins
(``checks_paged``), the ensemble by a descent of densified rows; the
system is reached through ``system.py`` and ``system_paged.py``.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import (checks, checks_paged, datagen_onehot, system,
                       system_paged)


def setup(ctx) -> None:
    p = ctx.params
    model = system.new_model(ctx, p["n_trees"])
    system_paged.compile_round_program(model, ctx.config["rows"],
                                       ctx.config["features"])
    blocks, pages = system_paged.stage_pages(ctx)
    handle = system_paged.ingest_paged(model, pages, ctx.config["features"],
                                       ctx.config["slab_rows"])
    pages.drop()
    model.param.n_trees = int(p["warm_trees"])
    model.fit_device(handle)
    model.param.n_trees = int(p["n_trees"])
    ctx.state.update(blocks=blocks, model=model, handle=handle,
                     y=np.concatenate([b[3] for b in blocks]),
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
    model, blocks, y = st["model"], st["blocks"], st["y"]
    n, F = len(y), int(cfg["features"])
    cuts = np.asarray(model.cuts)
    ctx.say(f"[bench] round_plan {ctx.counters.get('round_plan')}")
    t0 = time.perf_counter()
    bins_t = system_paged.fetch_feature_rows(st["handle"]["bins_t"], n)
    ctx.say(f"[bench] binned matrix fetched in "
            f"{time.perf_counter() - t0:.3f} s")
    last = system.host_trees(st["last"])
    numbers = {"rounds_share": len(last) / float(p["n_trees"])}
    # the binned matrix the trees were grown on is the pages' own rows,
    # an absent entry binned as 0.0
    rng = np.random.default_rng(ctx.seed)
    k = min(int(p["check_bin_rows"]), n)
    lo = int(rng.integers(0, n - k + 1))
    numbers["bins_mismatches"] = checks_paged.bins_mismatches(
        checks_paged.dense_rows(blocks, lo, k, F), bins_t[:, lo:lo + k], cuts)
    # the cuts the staging sketched, from ALL the rows: this cell holds
    # the configuration's cut guarantee itself (no ingest cell runs)
    ids = list(range(datagen_onehot.NUMERIC))
    numbers.update(checks_paged.cut_numbers(
        checks_paged.numeric_columns(blocks, ids), ids,
        checks_paged.occupied_indicators(blocks, n, F), cuts))
    if len(last) < 2:
        checks.apply_limits(ctx, numbers)      # nothing was boosted
        return
    numbers.update(checks_paged.boost_tree_numbers(bins_t, y, last, cfg))
    # two fits of one handle give byte-identical trees: the window's first
    # and last operation, and the warm fit against the rounds it shares
    numbers["ops_trees_differ"] = (
        checks.trees_differ(st["first"], st["last"])
        + checks.trees_differ(st["warm"], st["last"][:len(st["warm"])]))
    del bins_t
    Xh, yh = system_paged.heldout_rows(ctx, int(p["check_heldout_rows"]))
    m = min(int(p["check_train_rows"]), n)
    numbers.update(checks.learning_numbers(
        checks_paged.dense_rows(blocks, 0, m, F), y[:m], Xh, yh, cuts, last, cfg))
    # one predict of the densified held-out rows, as a user scores them
    got = np.asarray(model.predict(Xh.astype(np.float32)))
    numbers["score_gap"] = checks.score_gap([Xh], [got], cuts, last, cfg)
    checks.apply_limits(ctx, numbers)
