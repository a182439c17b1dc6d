"""Operation ``ingest_paged``: one whole ``make_device_data_iter`` over
``DiskRowIter`` pages — pass 1 the streaming sketch of every slab, pass 2
each slab binned onto the chip — on a fresh model with no ``cuts=``,
timed until every array of the handle is ready.  The handle of one
operation is dropped before the next begins.

Set-up builds the model first (a program that cannot run the cell fails
before a row is drawn), draws the CSR rows from the seed
(``datagen_onehot``), builds the page cache on local disk from them
(``setup.ingest_pages_s``) and runs one warm operation of the same
shape.  The window counts rows of work; no dense matrix of the table
exists on the host at any time, only slabs.  The check is against
``reference_paged``; the system is reached through ``system.py`` (the
model) and ``system_paged.py`` (pages, slabs, the iterator ingest).
"""

from __future__ import annotations

import numpy as np

from benchmark import (checks, checks_paged, datagen_onehot,
                       reference_paged as refp, system, system_paged)
from benchmark.metrics import _oplog


def _one(ctx):
    model = system.new_model(ctx, 1)
    ctx.state["models"].append(model)
    handle = system_paged.ingest_paged(
        model, ctx.state["pages"], ctx.config["features"],
        ctx.config["slab_rows"])
    return model, handle


def finish(ctx) -> None:
    """No operation fits, so nothing joins the background compile each
    ingest starts: wait for them here, outside the window."""
    for model in ctx.state["models"]:
        system.join_background(model)
    ctx.state["record"] = system_paged.last_ingest_record()
    ctx.state["pages"].drop()          # the reader thread and the file


def setup(ctx) -> None:
    ctx.state.update(models=[], model=None, handle=None)
    # every ingest starts the compile of its model's round program in
    # the background: a program that cannot compile it fails here
    system_paged.compile_round_program(
        system.new_model(ctx, 1), ctx.config["rows"], ctx.config["features"])
    blocks, pages = system_paged.stage_pages(ctx)
    ctx.state.update(blocks=blocks, pages=pages,
                     y=np.concatenate([b[3] for b in blocks]))
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
    blocks, n, F = st["blocks"], len(st["y"]), int(cfg["features"])
    cuts = np.asarray(st["model"].cuts)
    rng = np.random.default_rng(ctx.seed)
    ids = sorted(rng.choice(datagen_onehot.NUMERIC,
                            size=int(p["check_features"]),
                            replace=False).tolist())
    k = min(int(p["check_bin_rows"]), n)
    lo = int(rng.integers(0, n - k + 1))
    block = system.fetch_columns(st["handle"]["bins_t"], lo, k)
    slabs = -(-n // int(cfg["slab_rows"]))
    if st.get("record"):
        ctx.say(_oplog.line(st["record"]))     # the last operation's spans
    ctx.say(f"[bench] eps of {slabs} slabs "
            f"{refp.sketch_eps(int(cfg['n_summary']), slabs):.6f}")
    numbers = {
        "rows_share": st["handle"]["n"] / float(cfg["rows"]),
        # of the last operation: every page of the cache, twice
        "pages_replayed": st["pages"].replayed / float(st["pages"].count),
        "bins_mismatches": checks_paged.bins_mismatches(
            checks_paged.dense_rows(blocks, lo, k, F), block, cuts),
    }
    numbers.update(checks_paged.cut_numbers(
        checks_paged.numeric_columns(blocks, ids), ids,
        checks_paged.occupied_indicators(blocks, n, F),
        cuts))
    checks.apply_limits(ctx, numbers)
