"""The mean observation of one or more histogram series of ``/metrics``
between the window's two ends, summed, times ``scale`` (1000: seconds to
ms). ``series`` is a list of ``{"name": ..., "labels": {...}}``."""

import prom


def read(ctx, series: list, scale: float = 1.0):
    if ctx.before is None:
        return None
    total = 0.0
    for s in series:
        mean = prom.mean_delta(ctx.after, ctx.before, s["name"],
                               **s.get("labels", {}))
        if mean is None:
            return None
        total += mean
    return total * scale
