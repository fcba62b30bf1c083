"""The merge-phase rule (README.md): how many delta merges the design puts
into a window. ``TpuScanner`` merges its delta into the device mirror every
``T`` written rows, by count alone, and the merge stalls every write; so a
cell fixes the delta's fill when the window opens (``warmup_writes``, counted)
and its write rate, and with them the number of threshold crossings inside
the window. Closed-loop readers of the device path may add follow-up merges
behind a crossing (``followup_readers``): the rule allows those and does not
require them.

A Compact publishes the delta: ``TpuScanner.compact`` first merges whatever
the delta holds (``_ensure_published(full=True)``: one merge, and the
compactor's own Txn on ``compact_rev_key`` has just put a row there), then
folds the rows written during its pass into the compacted mirror. So the
fill restarts from about 0 once the pass is over, somewhere between the
Compact's due time and ``stall_s`` later, and every later crossing moves with
it. While the pass holds the merge lock (``_compact_active``) no reader
merges, so a Compact's merge has no follow-ups.
"""

from __future__ import annotations

import os

import plugin

#: ``TpuScanner(merge_threshold=)``'s default, which the README server runs
#: with; tests/test_mergephase.py fails if the engine's is another
MERGE_THRESHOLD = 4096
#: how long a merge's (or a Compact's) stall and backlog may last before
#: the rule counts them as over
STALL_S = 7.0

_OPS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "ops")


def _op(op: dict):
    return plugin.load(_OPS, op["op"])


def write_share(stream: dict) -> float:
    """The share of a stream's operations that write a row."""
    ops = stream.get("ops", ())
    total = sum(int(op.get("weight", 1)) for op in ops)
    writes = sum(int(op.get("weight", 1)) for op in ops if _op(op).WRITES)
    return writes / total if total else 0.0


def write_rate(traffic: dict, rate_scale: float = 1.0) -> tuple[float, float]:
    """(lowest, highest) rows written per second: the open loops' fixed
    rates, plus what the file says its closed loops reach."""
    lo = hi = 0.0
    closed = False
    for s in traffic["streams"]:
        share = write_share(s)
        if not share:
            continue
        if s["loop"] == "open":
            lo += float(s["rate"]) * rate_scale * share
            hi += float(s["rate"]) * rate_scale * share
        else:
            closed = True
    if closed:
        span = traffic["closed_loop_writes_per_s"]
        lo, hi = lo + float(span["min"]), hi + float(span["max"])
    return lo, hi


def compacts(traffic: dict, seconds: float, rate_scale: float = 1.0) -> list[float]:
    """The seconds of the window at which a Compact is due: an open-loop
    stream whose operations compact (``COMPACTS``) places them by its rate
    and phase, as ``run.plan_workers`` and ``worker.py`` place any open
    loop's requests."""
    out = []
    for s in traffic["streams"]:
        kinds = {bool(getattr(_op(op), "COMPACTS", False)) for op in s.get("ops", ())}
        if True not in kinds:
            continue
        if kinds != {True} or s.get("loop") != "open":
            raise ValueError(f"stream {s.get('name')}: a Compact stands alone "
                             "in an open-loop stream")
        procs = int(s.get("procs", 1))
        gap = procs / (float(s["rate"]) * rate_scale)
        for p in range(procs):
            due = gap * (p + float(s.get("phase", 0.0))) / procs
            while due < seconds:
                out.append(due)
                due += gap
    return sorted(out)


def merges(r: int, w: float, seconds: float, t: int = MERGE_THRESHOLD) -> int:
    """Merges that START inside a window of ``seconds`` at ``w`` rows/s when
    the delta holds ``r`` rows as it opens: one per ``t`` rows."""
    return int((r + w * seconds) // t)


def segments(traffic: dict, seconds: float, w: float, late: bool,
             stall_s: float = STALL_S) -> list[tuple[float, float, int]]:
    """The window cut at its Compacts: ``(start, end, fill at start)``, the
    rows counting from the warm-up's residue, and after a Compact from 0 at
    its due time or, ``late``, once its stall is over."""
    out, start, fill = [], 0.0, int(traffic.get("warmup_writes", 0))
    for c in compacts(traffic, seconds):
        out.append((start, max(start, c), fill))
        start, fill = c + (stall_s if late else 0.0), 0
    out.append((start, max(start, seconds), fill))
    return out


def crossings(traffic: dict, seconds: float, rate_scale: float = 1.0,
              t: int = MERGE_THRESHOLD) -> tuple[int, int]:
    """The designed (fewest, most) threshold crossings k in a window of
    ``seconds``: the merges the WRITES kick, one each."""
    lo, hi = write_rate(traffic, rate_scale)
    return tuple(sum(merges(fill, w, end - start, t) for start, end, fill
                     in segments(traffic, seconds, w, late))
                 for w, late in ((lo, True), (hi, False)))


def followup_readers(traffic: dict) -> int:
    """R: the clients of the mix's closed-loop streams that hold an operation
    the device answers (``DEVICE_READ``). ``TpuScanner._ensure_published``
    lets a reader that finds the delta at or over the threshold merge it
    itself: while the merge a crossing kicked is in flight the delta still
    reads full, so each such caller may park on the merge lock once and then
    merge the tail that has gathered — at most one follow-up merge a caller
    a crossing. An open loop stays out (its reads have never added a merge in
    a recorded run), and so does a closed loop of writers."""
    return sum(int(s["clients"]) for s in traffic["streams"]
               if s.get("loop") == "closed" and any(
                   _op(op).DEVICE_READ for op in s["ops"]))


def expected(traffic: dict, seconds: float, rate_scale: float = 1.0,
             t: int = MERGE_THRESHOLD) -> tuple[int, int]:
    """The (fewest, most) merges a run inside the design counts: the k
    crossings and up to R reader follow-ups behind each, and one merge for
    each Compact. A program that does not merge on the read path counts the
    least."""
    lo, hi = crossings(traffic, seconds, rate_scale, t)
    n = len(compacts(traffic, seconds, rate_scale))
    return lo + n, hi * (1 + followup_readers(traffic)) + n


def design_faults(traffic: dict, seconds: float, stall_s: float = STALL_S,
                  t: int = MERGE_THRESHOLD) -> list[str]:
    """What the traffic file's design breaks of the rule, for an open-loop
    cell at its own window length; empty where it holds.

    Per stretch between the window's ends and its Compacts, with fill r at
    its start and k crossings in it:
    k = 0, the last stretch: r + w*len <= t/2 (half the threshold is the
            margin);
    k >= 1: crossing i starts at (i*t - r)/w; the last one's stall and
            backlog (``stall_s``) are over before the window closes, or the
            Compact that ends the stretch is due;
    and, but for a last stretch with k = 0, the next crossing is far, after
    the window's end or the Compact's due time: (k+1)*t - r > w*len + t/4.
    A Compact's stall is over before the window closes, and the count holds
    whether its pass lasts 0 or ``stall_s``."""
    want = traffic["merges_in_window"]
    if isinstance(want, dict):
        lo, hi = crossings(traffic, seconds, t=t)
        return [] if (want["min"], want["max"]) == (lo, hi) else [
            f"the file's range {want} is not the rule's {lo}..{hi}"]
    w = write_rate(traffic)[0]
    lo, hi = crossings(traffic, seconds, t=t)
    out = []
    if (lo, hi) != (want, want):
        r = int(traffic.get("warmup_writes", 0))
        out.append(f"floor(({r} + {w:g} x {seconds:g}) / {t}) = {hi}"
                   + (f" ({lo} if a Compact's pass lasts {stall_s:g} s)"
                      if lo != hi else "") + f", the file says {want}")
    cs = compacts(traffic, seconds)
    for c in cs:
        if c + stall_s > seconds:
            out.append(f"the Compact at {c:.1f} s: its stall is not over by "
                       f"{seconds:g} s")
    stretches = segments(traffic, seconds, w, late=False)
    before_last = 0
    for n, (start, end, r) in enumerate(stretches):
        span, last_stretch = end - start, n == len(stretches) - 1
        # the last stretch holds what the file's count leaves to it
        k = want - before_last if last_stretch else merges(r, w, span, t)
        before_last += k
        if k < 0:
            continue
        if k == 0 and last_stretch:
            if r + w * span > t / 2:
                out.append(f"r + w*W = {r + w * span:g} > T/2 = {t / 2:g}")
            continue
        last = start + (k * t - r) / w
        if k and last + stall_s > end:
            what = f"{seconds:g} s" if last_stretch else f"the Compact at {end:.1f} s"
            out.append(f"merge {k} starts at {last:.1f} s: its stall and "
                       f"backlog are not over by {what}")
        # no crossing close behind the stretch's last one, nor just after
        # the Compact that ends it
        if (k + 1) * t - r <= w * span + t / 4:
            out.append(f"(k+1)*T - r = {(k + 1) * t - r} <= w*W + T/4 = "
                       f"{w * span + t / 4:g}")
    return out
