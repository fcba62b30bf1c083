"""Requests of one family (``range`` or ``txn``) acknowledged inside the
window, over all the window's seconds: a rate taken over all the work and
all the time, judged streams only."""

FAMILY = {"range": 0, "txn": 1}


def read(ctx, family: str):
    lo, hi = ctx.window
    acked = sum(1 for r in ctx.recs(FAMILY[family], due_in_window=False)
                if r[5] and lo <= r[4] < hi)
    return acked / ctx.window_s if acked else None
