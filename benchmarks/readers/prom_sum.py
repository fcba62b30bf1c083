"""The seconds that one or more histogram series of ``/metrics`` accumulated
between the window's two ends (the movement of their ``_sum``), added up,
times ``scale`` (1000: seconds to ms): how much of the window was spent
there in all, where ``prom_mean`` says how long one observation took. For
what happens a designed number of times a window (a merge, a count poll): 0
is a reading, nothing of the kind ran, where a mean would have nothing to
read. ``series`` is a list of ``{"name": ..., "labels": {...}}``."""

import prom


def read(ctx, series: list, scale: float = 1.0):
    if ctx.before is None:
        return None
    return scale * sum(
        prom.delta(ctx.after, ctx.before, s["name"] + "_sum",
                   **s.get("labels", {})) for s in series)
