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

How long a merge's stall and backlog last is the file's to state
(``merge_stall_s``, ``STALL_S`` where it states none): at a rate near the
write path's knee the crossings come closer together than ``STALL_S``
allows. A file that states its own allowance rests it on a measurement: the
longest stall and backlog measured on the chip at its store size and write
rate (``merge_stall_measured_s``, by ``stalls`` below), at least
``FLOOR_FACTOR`` times over, and its ``merge_rule`` text cites the figure.
Every run then holds the window's crossings to the allowance (``stalls``:
``run.py`` prints a loud line where one overruns it). A Compact's allowance
stays ``STALL_S`` whatever the file says.
"""

from __future__ import annotations

import os
import statistics

import plugin

#: ``TpuScanner(merge_threshold=)``'s default, which the README server runs
#: with; tests/test_mergephase.py fails if the engine's is another
MERGE_THRESHOLD = 4096
#: how long a Compact's stall and backlog may last before the rule counts
#: them as over, and a merge's where the traffic file states no allowance
STALL_S = 7.0
#: a file's own merge allowance is at least this many times the longest
#: stall and backlog measured on the chip at its size and rate
FLOOR_FACTOR = 2.0
#: a second of the window holds the writers up when its worst Txn took more
#: than this many times the window's median worst Txn of a second
HELD_UP = 2.0

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


def merge_stall_s(traffic: dict) -> float:
    """How long a merge's stall and backlog may last in this mix: the
    file's ``merge_stall_s``, else ``STALL_S``."""
    return float(traffic.get("merge_stall_s", STALL_S))


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


def crossing_times(traffic: dict, seconds: float, rate_scale: float = 1.0,
                   t: int = MERGE_THRESHOLD) -> list[float]:
    """The seconds of the window at which the writes kick a merge: at the
    highest designed rate, a Compact's pass taken as over at its due time
    (the earliest each crossing can come)."""
    w = write_rate(traffic, rate_scale)[1]
    out: list[float] = []
    if not w:
        return out
    for start, end, fill in segments(traffic, seconds, w, late=False):
        i = fill // t + 1
        while start + (i * t - fill) / w < end:
            out.append(start + (i * t - fill) / w)
            i += 1
    return out


def stalls(worst_by_second: list[float], crossings: list[float]) -> list[float]:
    """Each crossing's stall and backlog as the writers saw it, in seconds:
    from the crossing to the end of the run of seconds, from the crossing's
    own on, whose worst Txn took more than ``HELD_UP`` times the window's
    median worst Txn of a second (``worst_by_second``, the line ``run.py``
    prints, 0 for a second with no Txn). 0 where the crossing's own second
    held nobody up."""
    if not worst_by_second:
        return []
    bar = HELD_UP * statistics.median(worst_by_second)
    out = []
    for c in crossings:
        end = int(c)
        while end < len(worst_by_second) and worst_by_second[end] > bar:
            end += 1
        out.append(max(0.0, end - c))
    return out


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


def design_faults(traffic: dict, seconds: float, stall_s: float | None = None,
                  t: int = MERGE_THRESHOLD) -> list[str]:
    """What the traffic file's design breaks of the rule, for an open-loop
    cell at its own window length; empty where it holds.

    Per stretch between the window's ends and its Compacts, with fill r at
    its start and k crossings in it:
    k = 0, the last stretch: r + w*len <= t/2 (half the threshold is the
            margin);
    k >= 1: crossing i starts at (i*t - r)/w; the last one's stall and
            backlog (``stall_s``, the file's ``merge_stall_s`` where not
            given) are over before the window closes, or the Compact that
            ends the stretch is due;
    and, but for a last stretch with k = 0, the next crossing is far, after
    the window's end or the Compact's due time: (k+1)*t - r > w*len + t/4.
    A Compact's stall is over before the window closes, and the count holds
    whether its pass lasts 0 or ``STALL_S``. A file that states its own
    merge allowance states the measurement under it, and the allowance is at
    least ``FLOOR_FACTOR`` times that."""
    if stall_s is None:
        stall_s = merge_stall_s(traffic)
    want = traffic["merges_in_window"]
    if isinstance(want, dict):
        lo, hi = crossings(traffic, seconds, t=t)
        return allowance_faults(traffic) + (
            [] if (want["min"], want["max"]) == (lo, hi) else
            [f"the file's range {want} is not the rule's {lo}..{hi}"])
    w = write_rate(traffic)[0]
    lo, hi = crossings(traffic, seconds, t=t)
    out = allowance_faults(traffic)
    if (lo, hi) != (want, want):
        r = int(traffic.get("warmup_writes", 0))
        out.append(f"floor(({r} + {w:g} x {seconds:g}) / {t}) = {hi}"
                   + (f" ({lo} if a Compact's pass lasts {STALL_S:g} s)"
                      if lo != hi else "") + f", the file says {want}")
    cs = compacts(traffic, seconds)
    for c in cs:
        if c + STALL_S > seconds:
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


def allowance_faults(traffic: dict) -> list[str]:
    """Where the file states its own merge allowance: the measurement under
    it is stated too and cited in ``merge_rule``, and the allowance is at
    least ``FLOOR_FACTOR`` times it."""
    if "merge_stall_s" not in traffic:
        return []
    allowance = merge_stall_s(traffic)
    measured = traffic.get("merge_stall_measured_s")
    if measured is None:
        return ["merge_stall_s without merge_stall_measured_s, the longest "
                "stall and backlog measured on the chip"]
    out = []
    if allowance < FLOOR_FACTOR * float(measured):
        out.append(f"merge_stall_s {allowance:g} < {FLOOR_FACTOR:g} x "
                   f"merge_stall_measured_s {measured:g}")
    if f"{float(measured):g}" not in traffic.get("merge_rule", ""):
        out.append(f"merge_rule does not cite the measured {float(measured):g} s")
    return out
