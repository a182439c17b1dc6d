"""Device milliseconds of ONE expansion of a leaf-wise round: self time
under the program's ``dmlc.round.expand.*`` scopes (``pick``: the queue,
the row read and the node update; ``hist``: the single-node build and its
sync; ``settle``: the subtraction, the two split scans, the pool and queue
writes) and under ``dmlc.hist.*`` (the kernel's own pad and unpack, which
only the builds enter), over rounds x the plan's expansions a tree.  A
program without the scopes, or a plan without expansions, gives nothing."""

from benchmark.metrics import _spans


def read(ctx):
    plan = ctx.counters.get("round_plan") or {}
    t = _spans.scope_seconds(
        ctx, lambda s: s.startswith("dmlc.round.expand."))
    if t is None or not plan.get("expansions"):
        return None
    t += _spans.scope_seconds(ctx, lambda s: s.startswith("dmlc.hist.")) or 0.0
    return _spans.per(t, sum(ctx.op_work) * plan["expansions"], 1e3)
