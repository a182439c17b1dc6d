"""Share of the pair slots a ranking round computes that no pair of the
data needs: ``1 - rank_pairs / rank_pair_slots`` of the program's own
``round_plan`` (sum over the queries of G_q^2 against what the width
buckets compute), in percent."""


def read(ctx):
    plan = ctx.counters.get("round_plan") or {}
    if not plan.get("rank_pair_slots"):
        return None
    return 100.0 * (1.0 - plan["rank_pairs"] / plan["rank_pair_slots"])
