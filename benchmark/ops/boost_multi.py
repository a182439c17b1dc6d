"""Operation ``boost_multi``: operation ``boost`` on a table of several
classes — one whole ``fit_device`` on a device-resident handle under
``multi:softmax``, so every round grows one tree a class from gradients
that couple the classes through the softmax over a row's margins.

The window is ``ops/boost.py``'s, word for word: set-up stages all the
configuration's rows through ``make_device_data`` (no ``cuts=``) and runs
one warm fit of ``warm_trees`` rounds, the same program the window
dispatches; every operation boosts ``n_trees`` rounds into a fresh
ensemble and counts ``n_trees`` ROUNDS of work (as XGBoost counts
``num_boost_round``: a round here is ``num_class`` trees); its wall runs
from entering ``fit_device`` to the last chunk's trees on the host.  The
rows are ``datagen_multi``'s and the check is against
``reference_multi``; the system is reached through ``system.new_model``
and ``system.ingest`` as they stand — ``num_class`` is no key of
``system.MODEL_KEYS``: the model learns it from the labels.  Set-up
builds the model BEFORE it draws a row, so a program that cannot run the
cell (one whose constructor refuses ``multi:softmax`` without
``num_class``) fails at once.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import checks, checks_multi, datagen_multi, system


def _rows(ctx, rows: int, stream: int):
    return datagen_multi.covtype_like(int(rows), ctx.seed, stream=stream,
                                      features=int(ctx.config["features"]))


def setup(ctx) -> None:
    p = ctx.params
    model = system.new_model(ctx, p["n_trees"])
    X, y = _rows(ctx, ctx.config["rows"], 0)
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
    bins_t = np.asarray(st["handle"]["bins_t"])
    ctx.say(f"[bench] binned matrix fetched in "
            f"{time.perf_counter() - t0:.3f} s")
    last = system.host_trees(st["last"])
    # a round is one entry of the model's list (its arrays K trees)
    numbers = {"rounds_share": len(last) / float(p["n_trees"]),
               "rows_share": n / float(bins_t.shape[1])}
    bins_t = bins_t[:, :n]
    # the binned matrix the trees were grown on is the raw rows' own, on
    # a seeded block of ALL the columns — the indicators and the
    # hillshades among them, whose values sit ON their cuts: a value
    # equal to a cut counts it (bin = number of cuts <= x)
    rng = np.random.default_rng(ctx.seed)
    k = min(int(p["check_bin_rows"]), n)
    lo = int(rng.integers(0, n - k + 1))
    numbers["bins_mismatches"] = checks.bins_mismatches(
        X[lo:lo + k], bins_t[:, lo:lo + k], cuts)
    if len(last) < 2 or bins_t.shape[1] != n:
        checks.apply_limits(ctx, numbers)      # nothing to compare rows on
        return
    worst_leaf = {}
    numbers.update(checks_multi.boost_tree_numbers(bins_t, y, last, cfg,
                                                   worst_leaf))
    ctx.say(f"[bench] worst leaf of a round's trees, compared with nothing: "
            f"{worst_leaf}")
    # two fits of one handle give byte-identical trees: the window's first
    # and last operation, and the warm fit against the rounds it shares
    numbers["ops_trees_differ"] = (
        checks.trees_differ(st["first"], st["last"])
        + checks.trees_differ(st["warm"], st["last"][:len(st["warm"])]))
    Xh, yh = _rows(ctx, int(p["check_heldout_rows"]), 1)
    # ONE predict of the held-out rows, [n, K] margins, against a plain
    # float64 descent of the same trees
    numbers["score_gap"] = checks_multi.score_gap(
        Xh, model.predict(Xh, output_margin=True), cuts, last, cfg)
    m = min(int(p["check_train_rows"]), n)
    numbers.update(checks_multi.learning_numbers(X[:m], y[:m], Xh, yh, cuts,
                                                 last, cfg))
    checks.apply_limits(ctx, numbers)
