"""The chip's peaks (``peaks.json``, keyed by ``device_kind``) and the bytes
a read dispatch cannot avoid. Kept with the benchmark, so that no PR that
claims a gain can move the yardstick."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; a kind that is not in
    the table is an error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise KeyError(f"peaks.json has no device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def padded_rows(rows: int) -> int:
    """Rows the one-partition mirror holds on the device: the next power of
    two (``storage/tpu/blocks.padded_capacity``'s rule, restated)."""
    n = 1
    while n < max(1, rows):
        n *= 2
    return n


def read_bytes(dispatches: float, queries: float, mirror_bytes: float,
               rows_padded: int) -> float:
    """Bytes the read path must move for ``queries`` Ranges answered in
    ``dispatches`` scans: every stored column of the mirror read once per
    dispatch (``kb_mirror_bytes``: padded rows x stored bytes per row, from
    the mirror's own shapes) plus one mask byte per row per query written.
    It does not count the index extraction's traffic, which an algorithm
    could avoid."""
    return dispatches * mirror_bytes + queries * rows_padded
