"""What the slowest chip costs the others: device time of the all-reduce
operations per boosting round, largest over the chips less smallest.  An
all-reduce ends on every chip when the last chip has arrived, so the chip
that arrives first waits longest.  Nothing to read on one chip."""

from benchmark.metrics import _names


def read(ctx):
    per_chip = [sum(s for n, s in d.op_self_s.items()
                    if _names.is_all_reduce(n))
                for d in ctx.summary.devices]
    rounds = sum(ctx.op_work)
    if len(per_chip) < 2 or not max(per_chip) or not rounds:
        return None
    return 1e3 * (max(per_chip) - min(per_chip)) / rounds
