"""The server's ``/metrics`` exposition, read as an operator's scrape reads
it: a tiny text parser and the delta arithmetic between the window's two
ends. (Copied from ``kubebrain_tpu/workload/slo.py``, PERF.md section 7.)
"""

from __future__ import annotations

import re
import urllib.request

_SERIES_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+([^\s]+)\s*$')
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> dict:
    """name -> list of (labels, value)."""
    out: dict[str, list[tuple[dict, float]]] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SERIES_RE.match(line)
        if not m:
            continue
        name, raw_labels, raw_value = m.groups()
        try:
            value = float(raw_value)
        except ValueError:
            continue
        labels = dict(_LABEL_RE.findall(raw_labels)) if raw_labels else {}
        out.setdefault(name, []).append((labels, value))
    return out


def scrape(info_port: int) -> dict:
    url = f"http://127.0.0.1:{info_port}/metrics"
    with urllib.request.urlopen(url, timeout=10) as r:
        return parse(r.read().decode())


def series_sum(snap: dict, name: str, **want: str) -> float:
    """Sum of the series under ``name`` (or ``name_total``, the client
    library's counter suffix) whose labels match ``want``."""
    for candidate in (name, name + "_total"):
        hits = [v for labels, v in snap.get(candidate, ())
                if all(labels.get(k) == w for k, w in want.items())]
        if hits:
            return sum(hits)
    return 0.0


def delta(after: dict, before: dict, name: str, **want: str) -> float:
    return series_sum(after, name, **want) - series_sum(before, name, **want)


def mean_delta(after: dict, before: dict, name: str, **want: str):
    """Mean observation of a histogram or timer between two scrapes:
    delta of ``_sum`` over delta of ``_count``; None where nothing was
    observed."""
    n = delta(after, before, name + "_count", **want)
    if n <= 0:
        return None
    return delta(after, before, name + "_sum", **want) / n
