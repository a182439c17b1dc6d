"""Operation ``ingest_nan``: operation ``ingest`` on a table with holes —
one whole ``make_device_data`` of host float32 rows, most of them NaN, to
cuts over the values that are there to a binned, device-resident handle,
on a fresh model, timed until every array of the handle is ready.  The
handle of one operation is dropped before the next begins.  Set-up makes
the rows and runs one warm operation of the same shape.

The window is ``ops/ingest.py``'s, word for word: the same timing points,
the same work counted (rows).  The rows are ``datagen_missing``'s and the
check is against ``reference_missing``; the system is reached through
``system.py`` alone.
"""

from __future__ import annotations

import numpy as np

from benchmark import checks, checks_missing, datagen_missing, system


def _one(ctx):
    model = system.new_model(ctx, 1)
    ctx.state["models"].append(model)
    handle = system.ingest(model, ctx.state["X"], ctx.state["y"])
    return model, handle


def finish(ctx) -> None:
    """No operation fits, so nothing joins the background compile each
    ``make_device_data`` starts: wait for them here, outside the window."""
    for model in ctx.state["models"]:
        system.join_background(model)


def setup(ctx) -> None:
    X, y = datagen_missing.bosch_like(int(ctx.config["rows"]),
                                      int(ctx.config["features"]),
                                      ctx.seed, stream=0)
    ctx.state.update(X=X, y=y, model=None, handle=None, models=[])
    model, handle = _one(ctx)
    system.join_background(model)      # its compile events belong to set-up
    system.drop_handle(handle)


def op(ctx, i: int) -> float:
    if ctx.state["handle"] is not None:
        system.drop_handle(ctx.state["handle"])
    ctx.state["model"], ctx.state["handle"] = _one(ctx)
    return float(len(ctx.state["y"]))


def check(ctx) -> None:
    st, cfg, p = ctx.state, ctx.config, ctx.params
    X, n = st["X"], len(st["y"])
    cuts = np.asarray(st["model"].cuts)
    rng = np.random.default_rng(ctx.seed)
    feats = sorted(rng.choice(X.shape[1], size=int(p["check_features"]),
                              replace=False).tolist())
    k = min(int(p["check_bin_rows"]), n)
    lo = int(rng.integers(0, n - k + 1))
    block = system.fetch_columns(st["handle"]["bins_t"], lo, k)
    numbers = {
        "rows_share": st["handle"]["n"] / float(cfg["rows"]),
        "cuts_gap": checks_missing.cuts_gap(X, cuts, feats, cfg),
    }
    numbers.update(checks_missing.bin_numbers(X[lo:lo + k], block, cuts, cfg))
    checks.apply_limits(ctx, numbers)
