"""How many of the judged, answered requests of one family (``range`` or
``txn``) due in the window a witnessed pause of the machine touched: those
a ``*_quiet_ms`` tail leaves out and every end-to-end metric keeps. A count:
0 is a reading (the machine did not pause)."""

FAMILY = {"range": 0, "txn": 1}


def read(ctx, family: str):
    return sum(1 for r in ctx.recs(FAMILY[family])
               if r[5] and ctx.touched(r[2], r[4]))
