"""Operation ``score``: one ``model.predict`` call on a slab of held-out
rows, host array in, host array out, one caller.  Set-up stages the
training rows, boosts ``n_trees`` rounds, frees the training handle and
warms the slab's shape.  Every seed scores the same slabs, starting at
another one.
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
                     start=start, outputs=[])
    model.predict(Xh[:rows])


def _slab(ctx, i: int) -> slice:
    st = ctx.state
    lo = ((st["start"] + i) % st["n_slabs"]) * st["rows"]
    return slice(lo, lo + st["rows"])


def op(ctx, i: int) -> float:
    st = ctx.state
    st["outputs"].append((i, st["model"].predict(st["Xh"][_slab(ctx, i)])))
    return float(st["rows"])


def check(ctx) -> None:
    st, cfg, p = ctx.state, ctx.config, ctx.params
    model, outputs = st["model"], st["outputs"]
    trees = system.host_trees(model.trees)
    cuts = np.asarray(model.cuts)
    rng = np.random.default_rng(ctx.seed)
    want = min(len(outputs), math.ceil(int(p["check_rows"]) / st["rows"]))
    picked = sorted(rng.choice(len(outputs), size=want, replace=False))
    slabs = [st["Xh"][_slab(ctx, outputs[j][0])] for j in picked]
    got = [outputs[j][1] for j in picked]
    # every call's answers, against the labels: a model that scores
    # garbage fast is not a result
    seen = outputs[:st["n_slabs"]]
    scores = np.concatenate([o for _i, o in seen])
    labels = np.concatenate([st["yh"][_slab(ctx, i)] for i, _o in seen])
    checks.apply_limits(ctx, {
        "rows_share": min(len(o) for _i, o in outputs) / float(st["rows"]),
        "score_gap": checks.score_gap(slabs, got, cuts, trees, cfg),
        "heldout_auc": reference.auc(scores, labels),
    })
