"""A percentile, in ms, over every (event, watcher) pair of the window, of
the time from the instant the write was DUE (so it contains the commit, and
the wait a stall imposes) to that watcher receiving its event, both on the
machine's monotonic clock. ``quiet`` leaves out the pairs a witnessed pause
of the MACHINE touched anywhere between those two instants (the pause rule,
README.md): a per-layer reading, never an end-to-end metric's."""

from stats import percentile


def read(ctx, q: float, quiet: bool = False):
    due, arrived = ctx.watch_pairs()
    lags = (arrived - due) * 1e3
    if quiet:
        lags = lags[~ctx.touched(due, arrived)]
    return percentile(lags.tolist(), q) if len(lags) else None
