"""A percentile, in ms, of every judged request of one family (``range`` or
``txn``) that was due in the window, timed from when it was due (an open
loop's schedule; in a closed loop the moment it was sent). A request that
failed or was shed counts in ``failed`` and has no latency. ``quiet`` leaves
out the requests a witnessed pause of the MACHINE touched (the pause rule,
README.md: ``ctx.touched(due, done)``): a per-layer reading, never an
end-to-end metric's, which is taken over every request."""

from stats import percentile

FAMILY = {"range": 0, "txn": 1}


def read(ctx, family: str, q: float, quiet: bool = False):
    lat = [(r[4] - r[2]) * 1e3 for r in ctx.recs(FAMILY[family])
           if r[5] and not (quiet and ctx.touched(r[2], r[4]))]
    return percentile(lat, q) if lat else None
