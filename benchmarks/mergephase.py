"""The merge-phase rule (README.md): how many delta merges the design puts
into a window. ``TpuScanner`` merges its delta into the device mirror every
``T`` written rows, by count alone, and the merge stalls every write; so a
cell fixes the delta's fill when the window opens (``warmup_writes``, counted)
and its write rate, and with them the number of threshold crossings inside
the window. Closed-loop readers of the device path may add follow-up merges
behind a crossing (``followup_readers``): the rule allows those and does not
require them.
"""

from __future__ import annotations

import os

import plugin

#: ``TpuScanner(merge_threshold=)``'s default, which the README server runs
#: with; tests/test_mergephase.py fails if the engine's is another
MERGE_THRESHOLD = 4096

_OPS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "ops")


def write_share(stream: dict) -> float:
    """The share of a stream's operations that write a row."""
    ops = stream.get("ops", ())
    total = sum(int(op.get("weight", 1)) for op in ops)
    writes = sum(int(op.get("weight", 1)) for op in ops
                 if plugin.load(_OPS, op["op"]).WRITES)
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


def merges(r: int, w: float, seconds: float, t: int = MERGE_THRESHOLD) -> int:
    """Merges that START inside a window of ``seconds`` at ``w`` rows/s when
    the delta holds ``r`` rows as it opens: one per ``t`` rows."""
    return int((r + w * seconds) // t)


def crossings(traffic: dict, seconds: float, rate_scale: float = 1.0,
              t: int = MERGE_THRESHOLD) -> tuple[int, int]:
    """The designed (fewest, most) threshold crossings k in a window of
    ``seconds``: the merges the WRITES kick, one each."""
    r = int(traffic.get("warmup_writes", 0))
    lo, hi = write_rate(traffic, rate_scale)
    return merges(r, lo, seconds, t), merges(r, hi, seconds, t)


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
                   plugin.load(_OPS, op["op"]).DEVICE_READ for op in s["ops"]))


def expected(traffic: dict, seconds: float, rate_scale: float = 1.0,
             t: int = MERGE_THRESHOLD) -> tuple[int, int]:
    """The (fewest, most) merges a run inside the design counts: the k
    crossings, and up to R reader follow-ups behind each. A program that
    does not merge on the read path counts k."""
    lo, hi = crossings(traffic, seconds, rate_scale, t)
    return lo, hi * (1 + followup_readers(traffic))


def design_faults(traffic: dict, seconds: float, stall_s: float = 7.0,
                  t: int = MERGE_THRESHOLD) -> list[str]:
    """What the traffic file's design breaks of the rule, for an open-loop
    cell at its own window length; empty where it holds.

    k = 0:  r + w*W <= t/2 (half the threshold is the margin).
    k >= 1: merge i starts at (i*t - r)/w; the last one's stall and backlog
            (``stall_s``) are over before the window closes; and the next
            one is far: (k+1)*t - r > w*W + t/4."""
    want = traffic["merges_in_window"]
    if isinstance(want, dict):
        lo, hi = crossings(traffic, seconds, t=t)
        return [] if (want["min"], want["max"]) == (lo, hi) else [
            f"the file's range {want} is not the rule's {lo}..{hi}"]
    r = int(traffic.get("warmup_writes", 0))
    w = write_rate(traffic)[0]
    out = []
    if merges(r, w, seconds, t) != want:
        out.append(f"floor(({r} + {w:g} x {seconds:g}) / {t}) = "
                   f"{merges(r, w, seconds, t)}, the file says {want}")
    if want == 0:
        if r + w * seconds > t / 2:
            out.append(f"r + w*W = {r + w * seconds:g} > T/2 = {t / 2:g}")
    else:
        last = (want * t - r) / w
        if last + stall_s > seconds:
            out.append(f"merge {want} starts at {last:.1f} s: its stall and "
                       f"backlog are not over by {seconds:g} s")
        if (want + 1) * t - r <= w * seconds + t / 4:
            out.append(f"(k+1)*T - r = {(want + 1) * t - r} <= w*W + T/4 = "
                       f"{w * seconds + t / 4:g}")
    return out
