"""How far one counter of ``/metrics`` (every series under ``name`` whose
labels match) moved between the window's two ends. A count: 0 is a reading."""

import prom


def read(ctx, name: str, labels: dict | None = None):
    if ctx.before is None:
        return None
    return prom.delta(ctx.after, ctx.before, name, **(labels or {}))
