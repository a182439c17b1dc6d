"""Operation ``boost_rank``: operation ``boost`` on documents in query
groups — one whole ``fit_device`` on a device-resident handle that
carries its group table, so every round runs LambdaMART's gradient stage
(per-query ranks and pair sums) in front of the histogram kernels.

The window is ``ops/boost.py``'s, word for word: set-up draws the rows,
stages all of them through ``make_device_data`` (no ``cuts=``) and runs
one warm fit of ``warm_trees`` rounds, the same program the window
dispatches; every operation boosts ``n_trees`` rounds into a fresh
ensemble and counts ``n_trees`` rounds of work; its wall runs from
entering ``fit_device`` to the last chunk's trees on the host.  The rows
are ``datagen_rank``'s and the check is against ``reference_rank``; the
system is reached through ``system.new_model`` and the model's own
``make_device_data(X, y, qid=qid)`` — ``qid`` is an argument
``system.ingest`` does not know, and there is NO fall-back to
``fit(qid=)``: a program without the keyword raises ``TypeError`` at
once.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import checks, checks_rank, datagen_rank, system
from benchmark import reference_rank as rr


def _rows(ctx, queries, rows, stream):
    cfg = ctx.config
    return datagen_rank.mslr_like(int(queries), rows, int(cfg["features"]),
                                  ctx.seed, stream=stream,
                                  max_group=int(cfg["max_group"]))


def _ingest(model, X, y, qid):
    """``system.ingest`` with the query groups: the handle counts as made
    when every array of it, the group table's included, is ready."""
    import jax

    handle = model.make_device_data(X, y, qid=qid)
    jax.block_until_ready(jax.tree.leaves(handle))
    return handle


def setup(ctx) -> None:
    p, cfg = ctx.params, ctx.config
    X, y, qid = _rows(ctx, cfg["queries"], int(cfg["rows"]), 0)
    model = system.new_model(ctx, p["n_trees"])
    handle = _ingest(model, X, y, qid)
    model.param.n_trees = int(p["warm_trees"])
    model.fit_device(handle)
    model.param.n_trees = int(p["n_trees"])
    ctx.state.update(X=X, y=y, qid=qid, model=model, handle=handle,
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
    model, X, y, qid = st["model"], st["X"], st["y"], st["qid"]
    n = len(y)
    cuts = np.asarray(model.cuts)
    t0 = time.perf_counter()
    bins_t = np.asarray(st["handle"]["bins_t"])
    ctx.say(f"[bench] binned matrix fetched in "
            f"{time.perf_counter() - t0:.3f} s")
    last = system.host_trees(st["last"])
    # the handle holds every document once, in query order: its rows are
    # the data's (one chip pads nothing)
    order, bounds = rr.query_bounds(qid)
    numbers = {"rounds_share": len(last) / float(p["n_trees"]),
               "rows_share": n / float(bins_t.shape[1])}
    rel = y[order]
    rng = np.random.default_rng(ctx.seed)
    k = min(int(p["check_bin_rows"]), n)
    lo = int(rng.integers(0, n - k + 1))
    numbers["bins_mismatches"] = checks.bins_mismatches(
        X[order[lo:lo + k]], bins_t[:, lo:lo + k], cuts)
    if len(last) < 2 or bins_t.shape[1] != n:
        checks.apply_limits(ctx, numbers)      # nothing to compare rows on
        return
    numbers.update(checks_rank.boost_tree_numbers(bins_t, rel, bounds, last,
                                                  cfg))
    # two fits of one handle give byte-identical trees: the window's first
    # and last operation, and the warm fit against the rounds it shares
    numbers["ops_trees_differ"] = (
        checks.trees_differ(st["first"], st["last"])
        + checks.trees_differ(st["warm"], st["last"][:len(st["warm"])]))
    Xh, yh, qh = _rows(ctx, p["check_heldout_queries"], None, 1)
    oh, bh = rr.query_bounds(qh)
    m = int(bounds[min(int(p["check_train_queries"]), len(bounds) - 1)])
    numbers.update(checks_rank.learning_numbers(
        X[order[:m]], rel[:m], bounds[bounds <= m], Xh[oh], yh[oh], bh, cuts,
        last, cfg))
    checks.apply_limits(ctx, numbers)
