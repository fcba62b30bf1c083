"""A percentile, in ms, of every judged request of one family (``range`` or
``txn``) that was due in the window, timed from when it was due (an open
loop's schedule; in a closed loop the moment it was sent). A request that
failed or was shed counts in ``failed`` and has no latency. ``quiet`` leaves
out the requests a witnessed pause of the MACHINE touched (the pause rule,
README.md: ``ctx.touched(due, done)``): a per-layer reading, never an
end-to-end metric's, which is taken over every request."""

import numpy as np

from stats import percentile

FAMILY = {"range": 0, "txn": 1}


def read(ctx, family: str, q: float, quiet: bool = False):
    due, done = np.array([(r[2], r[4]) for r in ctx.recs(FAMILY[family])
                          if r[5]], np.float64).reshape(-1, 2).T
    lat = (done - due) * 1e3
    if quiet:
        lat = lat[~ctx.touched(due, done)]
    return percentile(lat.tolist(), q) if len(lat) else None
