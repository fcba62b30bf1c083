"""A witness of the machine: which stretches of a run did the MACHINE not
run, as opposed to the server?

A thread of ``run.py``'s own process — which only sleeps from the window's
opening to its close — sleeps ``TICK_S`` in a loop on ``time.monotonic()``,
the clock every process of a run shares, and records every overshoot of at
least ``PAUSE_MIN_S`` as an interval ``(from, to)``. It is neither the
server nor a load generator: a stall of the server (a merge, a Count's loop,
its GIL) cannot make it late; only a pause of the whole machine, or of the
container's CPU quota, can.

It judges nothing. Every end-to-end metric is taken over every request; the
witness says how much of a run the machine stood still
(``machine_pause_ms.*``) and, by **the pause rule** (README.md), which
requests that touched: a request is *touched by a pause* when its ``[due,
done]`` interval overlaps a witnessed ``[from, to + DRAIN * (to - from)]`` —
the pause, and ``DRAIN`` times as long again for the burst it leaves behind
to drain. The margin is a function of the pause's own length alone.
``touched`` below is the rule's one statement; ``run.Context.touched`` hands
it to the readers of the per-layer ``*_quiet_ms`` tails and ``touched_ops.*``.
"""

from __future__ import annotations

import threading
import time

from stats import percentile

PAUSE_MIN_S = 0.020   # README.md has the readings behind both constants
TICK_S = 0.001
# An open loop at rate r on a server that drains a burst at m/s needs
# r / (m - r) pause-lengths to be rid of a pause's backlog. The steady cell's
# 270 txn/s against ~340/s in a burst is 3.9 in an ordinary second (worst
# Txn due in the pause 121 ms, one length later 95, then 63, 31, and 4 in
# the fifth) and about ten where the pause meets a Count's Python loop, the
# server's slowest seconds (132, 126, 111, 97, 88, 70, 62 ...): PERF.md
# section 5. The margin covers the slower of the two. It is fitted to the
# program as PR 27 left it and goes stale with its drain rate: good enough
# for a reading that stands beside the tails, which is all it is.
DRAIN = 10


def touched(pauses, a: float, b: float) -> bool:
    """Does ``[a, b]`` overlap a witnessed pause or the drain after it?"""
    return any(a <= t + DRAIN * (t - f) and b >= f for f, t in pauses)


def clipped_total(pauses, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` that lie inside a witnessed pause."""
    return sum(max(0.0, min(t, hi) - max(f, lo)) for f, t in pauses)


class Witness:
    """``start()`` before the window's first instant, ``stop()`` after the
    drain; then ``pauses`` holds the witnessed intervals and ``overshoots``
    every tick's lateness."""

    def __init__(self, clock=time.monotonic, sleep=time.sleep):
        self._clock, self._sleep = clock, sleep
        self.pauses: list[tuple[float, float]] = []
        self.overshoots: list[float] = []
        self._started = False
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="pause-witness")

    def observe(self, before: float, after: float) -> None:
        """One tick: the clock read ``before`` going to sleep for ``TICK_S``
        and ``after`` on waking."""
        over = after - before - TICK_S
        self.overshoots.append(over)
        if over >= PAUSE_MIN_S:
            self.pauses.append((before, after))

    def _run(self) -> None:
        before = self._clock()
        while not self._stop.is_set():
            self._sleep(TICK_S)
            after = self._clock()
            self.observe(before, after)
            before = after

    def start(self) -> None:
        self._started = True
        self._thread.start()

    def stop(self) -> None:
        """Idempotent, and harmless on a witness that never started."""
        if not self._started or self._stop.is_set():
            return
        self._stop.set()
        self._thread.join(10.0)

    def line(self, window: tuple[float, float]) -> str:
        """The earlier line of every run (README.md)."""
        lo, hi = window
        inside = [(f, t) for f, t in self.pauses if t > lo and f < hi]
        out = (f"machine pauses: n={len(inside)} "
               f"total={clipped_total(inside, lo, hi) * 1e3:.1f} ms "
               f"max={max((t - f for f, t in inside), default=0.0) * 1e3:.1f} ms "
               f"at seconds [{', '.join(f'{f - lo:.2f}' for f, _t in inside)}]")
        after = [(f, t) for f, t in self.pauses if f >= hi]
        if after:
            out += (f" (+{len(after)} in the drain, "
                    f"{sum(t - f for f, t in after) * 1e3:.1f} ms)")
        if self.overshoots:
            out += (f"; witness: {len(self.overshoots)} ticks, overshoot "
                    f"p50={percentile(self.overshoots, 50) * 1e3:.3f} ms "
                    f"p99.9={percentile(self.overshoots, 99.9) * 1e3:.3f} ms, "
                    "largest under the threshold "
                    f"{max((o for o in self.overshoots if o < PAUSE_MIN_S), default=0.0) * 1e3:.3f} ms "
                    f"(a pause is >= {PAUSE_MIN_S * 1e3:g} ms)")
        return out
