"""A percentile, in ms, over every (event, watcher) pair of the window, of
the time from the instant the write was DUE (so it contains the commit, and
the wait a stall imposes) to that watcher receiving its event, both on the
machine's monotonic clock."""

from stats import percentile


def read(ctx, q: float):
    due = {r[6]: r[2] for r in ctx.recs(1, judged_only=False) if r[5]}
    lags = [(ev[4] - due[ev[0]]) * 1e3 for dump in ctx.watches
            for w in dump["watches"] for ev in w["events"] if ev[0] in due]
    return percentile(lags, q) if lags else None
