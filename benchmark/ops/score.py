"""Operation ``score``: one ``model.predict`` call on a slab of held-out
rows, host array in, host array out, one caller.  Set-up stages the
training rows, boosts ``n_trees`` rounds, frees the training handle and
warms the slab's shape.  Every seed scores the same slabs, starting at
another one.

Of the window's answers only what ``check`` reads is kept: the first lap
over the held-out rows, a sample of the calls drawn from the seed as they
come (a reservoir), and the shortest answer's length.  Holding every
answer (235 MB a window) made the calls it timed 5% slower: from the
window's second second on, the NaN scan inside ``predict`` took 0.45 ms
for 0.23 (PERF.md section 2, PR 32).
"""

from __future__ import annotations

import math

import numpy as np

from benchmark import checks, reference, system


def setup(ctx) -> None:
    p = ctx.params
    X, y = system.training_rows(ctx)
    model = system.new_model(ctx, p["n_trees"])
    handle = system.ingest(model, X, y)
    model.fit_device(handle)
    system.drop_handle(handle)
    del X, y
    Xh, yh = system.heldout_rows(ctx, int(p["heldout_rows"]))
    rows = int(p["slab_rows"])
    n_slabs = len(yh) // rows
    start = int(np.random.default_rng(ctx.seed).integers(0, n_slabs))
    ctx.state.update(model=model, Xh=Xh, yh=yh, rows=rows, n_slabs=n_slabs,
                     start=start, first_lap=[], sample=[], calls=0,
                     shortest=rows, draw=np.random.default_rng(ctx.seed),
                     want=math.ceil(int(p["check_rows"]) / rows))
    model.predict(Xh[:rows])


def _slab(ctx, i: int) -> slice:
    st = ctx.state
    lo = ((st["start"] + i) % st["n_slabs"]) * st["rows"]
    return slice(lo, lo + st["rows"])


def op(ctx, i: int) -> float:
    st = ctx.state
    out = st["model"].predict(st["Xh"][_slab(ctx, i)])
    st["shortest"] = min(st["shortest"], len(out))
    if len(st["first_lap"]) < st["n_slabs"]:
        st["first_lap"].append((i, out))
    # every call of the window is as likely to be among the ``want`` kept
    if len(st["sample"]) < st["want"]:
        st["sample"].append((i, out))
    else:
        j = int(st["draw"].integers(0, st["calls"] + 1))
        if j < st["want"]:
            st["sample"][j] = (i, out)
    st["calls"] += 1
    return float(st["rows"])


def check(ctx) -> None:
    st, cfg = ctx.state, ctx.config
    model = st["model"]
    trees = system.host_trees(model.trees)
    cuts = np.asarray(model.cuts)
    picked = sorted(st["sample"], key=lambda io: io[0])
    slabs = [st["Xh"][_slab(ctx, i)] for i, _o in picked]
    got = [o for _i, o in picked]
    # the first lap's answers, every held-out row once, against the
    # labels: a model that scores garbage fast is not a result
    seen = st["first_lap"]
    scores = np.concatenate([o for _i, o in seen])
    labels = np.concatenate([st["yh"][_slab(ctx, i)] for i, _o in seen])
    checks.apply_limits(ctx, {
        "rows_share": st["shortest"] / float(st["rows"]),
        "score_gap": checks.score_gap(slabs, got, cuts, trees, cfg),
        "heldout_auc": reference.auc(scores, labels),
    })
