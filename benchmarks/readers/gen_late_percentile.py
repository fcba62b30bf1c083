"""How late the open-loop generators sent: send time minus due time, in ms.
A starved generator must not read as a fast server."""

from stats import percentile


def read(ctx, q: float):
    late = [(r[3] - r[2]) * 1e3 for r in ctx.recs(loop="open", judged_only=False)]
    return percentile(late, q) if late else None
