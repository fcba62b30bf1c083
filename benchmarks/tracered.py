#!/usr/bin/env python3
"""The reduction from a profiler trace (``*.xplane.pb``) to the device's
numbers: the seconds in which an operation ran on each chip (the union of
the intervals of its ops), the operations that took most time, and the
longest gaps. Kept with the benchmark so that every PR computes the same
number in the same way.

It needs ``jax.profiler.ProfileData`` to read the file, so ``run.py`` runs it
as a process of its own with ``JAX_PLATFORMS=cpu``, after the server has
stopped: the parent never imports JAX and nobody else holds the chip.

    python benchmarks/tracered.py <trace-dir-or-file> [--describe]
"""

from __future__ import annotations

import glob
import json
import os
import sys

#: the line of a device plane that holds one event per executed operation
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


def load(path: str) -> list[dict]:
    """planes -> lines -> events as plain (name, start_ns, duration_ns)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    return [{"name": plane.name, "lines": [
        {"name": line.name, "events": [
            (ev.name, int(ev.start_ns), int(ev.duration_ns))
            for ev in line.events]} for line in plane.lines]}
        for plane in data.planes]


def union_ns(intervals: list[tuple[int, int]]) -> int:
    """Total length covered by the (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals: list[tuple[int, int]], lo: int, hi: int) -> list[int]:
    """The idle stretches of [lo, hi] between the merged intervals."""
    out, edge = [], lo
    for s, e in sorted(intervals):
        if s > edge:
            out.append(s - edge)
        edge = max(edge, e)
    if hi > edge:
        out.append(hi - edge)
    return out


def reduce(planes: list[dict], window_s: float | None = None) -> dict:
    """busy_s (averaged over the device planes), the traced window, the top
    operations and the longest idle gaps."""
    devices = [p for p in planes if p["name"].startswith(DEVICE_PREFIX)]
    if not devices:
        raise ValueError("the trace holds no device plane: "
                         + ", ".join(p["name"] for p in planes))
    lo = min((ev[1] for p in planes for ln in p["lines"] for ev in ln["events"]),
             default=0)
    hi = max((ev[1] + ev[2] for p in planes for ln in p["lines"]
              for ev in ln["events"]), default=0)
    busy, ops, gaps = [], {}, []
    for p in devices:
        events = [ev for ln in p["lines"] if ln["name"] == OPS_LINE
                  for ev in ln["events"]]
        spans = [(s, s + d) for _n, s, d in events]
        busy.append(union_ns(spans) / 1e9)
        for name, _s, d in events:
            tot, n = ops.get(name, (0, 0))
            ops[name] = (tot + d, n + 1)
        gaps += gaps_ns(spans, lo, hi)
    window = window_s if window_s else (hi - lo) / 1e9
    top = sorted(ops.items(), key=lambda kv: -kv[1][0])
    return {
        "window_s": window,
        "busy_s": sum(busy) / len(busy),
        "devices": len(devices),
        "ops": [[name, tot / 1e9, n] for name, (tot, n) in top],
        # no host span of the program is in the profiler's trace yet, so a
        # gap cannot be laid to what the host was doing (PERF.md section 7)
        "idle_gaps": [["unattributed", g / 1e9]
                      for g in sorted(gaps, reverse=True)[:10]],
    }


def describe(planes: list[dict]) -> str:
    out = []
    for p in planes:
        out.append(f"plane {p['name']!r}")
        for ln in p["lines"]:
            names: dict[str, list] = {}
            for name, _s, d in ln["events"]:
                names.setdefault(name, []).append(d)
            out.append(f"  line {ln['name']!r}: {len(ln['events'])} events, "
                       f"{len(names)} names")
            for name, ds in sorted(names.items(), key=lambda kv: -sum(kv[1]))[:25]:
                out.append(f"    {sum(ds) / 1e6:10.3f} ms  x{len(ds):<6} {name[:140]}")
    return "\n".join(out)


def main(argv: list[str]) -> int:
    planes = load(argv[0])
    if "--describe" in argv:
        print(describe(planes))
    else:
        print(json.dumps(reduce(planes)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
