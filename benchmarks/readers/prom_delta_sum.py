"""How far one or more counters of ``/metrics`` moved between the window's
two ends, added up: ``series`` is a list of ``{"name": ..., "labels":
{...}}`` (two kinds of one counter, say). A count: 0 is a reading."""

import prom


def read(ctx, series: list):
    if ctx.before is None:
        return None
    return sum(prom.delta(ctx.after, ctx.before, s["name"],
                          **s.get("labels", {})) for s in series)
