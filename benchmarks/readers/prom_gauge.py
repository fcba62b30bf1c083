"""One gauge (or any single sample) of ``/metrics`` as it stood at one end of
the window: ``at="before"`` is the scrape taken as the window opens,
``at="after"`` the one taken as it closes. The series under ``name`` whose
labels match are summed; nothing where the scrape or the series is absent
(a program older than the gauge), never 0."""


def read(ctx, name: str, labels: dict | None = None, at: str = "before"):
    if at not in ("before", "after"):
        raise ValueError(f"at={at!r}: 'before' or 'after'")
    snap = ctx.before if at == "before" else ctx.after
    if snap is None:
        return None
    want = labels or {}
    hits = [v for have, v in snap.get(name, ())
            if all(have.get(k) == w for k, w in want.items())]
    return sum(hits) if hits else None
