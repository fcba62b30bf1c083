"""The mean time, in ms, from a compactor tick's due time to its answer
(the tick's Txn and, where that succeeded, its Compact: one record of the
COMPACT family, ``worker.py``) over the ticks due in the window and
answered; nothing where the mix sent none."""

COMPACT = 2


def read(ctx):
    took = [(r[4] - r[2]) * 1e3
            for r in ctx.recs(COMPACT, judged_only=False) if r[5]]
    return sum(took) / len(took) if took else None
