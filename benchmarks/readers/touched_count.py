"""How many of the judged, answered requests of one family (``range`` or
``txn``) due in the window a witnessed pause of the machine touched: those
a ``*_quiet_ms`` tail leaves out and every end-to-end metric keeps. A count:
0 is a reading (the machine did not pause)."""

import numpy as np

FAMILY = {"range": 0, "txn": 1}


def read(ctx, family: str):
    due, done = np.array([(r[2], r[4]) for r in ctx.recs(FAMILY[family])
                          if r[5]], np.float64).reshape(-1, 2).T
    return int(ctx.touched(due, done).sum())
