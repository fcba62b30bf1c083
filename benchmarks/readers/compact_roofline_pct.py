"""The victim-mark kernel's share of the HBM roofline in the traced
seconds: the bytes one mark cannot avoid (``mark_bytes``) at the device
kind's peak bandwidth, over the kernel's own device time. The kernel is
the capture's op named ``victim_mask_batch_cached`` (the Pallas mark,
``kubebrain_tpu/ops/compact_pallas.py``); nothing where the capture holds
none (no Compact in the traced seconds, the jnp kernel, a program that
names it otherwise)."""

import re

import prom
import roofline

#: the op's own name (what stands before ``=`` in the capture), not an
#: operand of the fusions that read its mask
KERNEL = re.compile(r"^[%_]?victim_mask_batch_cached\b")


def padded_capacity(rows: int) -> int:
    """Rows a partition holding ``rows`` is padded to on the device: the
    next power of two past 1.25x headroom, at least 256
    (``kubebrain_tpu/storage/tpu/blocks.padded_capacity``'s rule,
    restated)."""
    want = max(256, int(rows * 1.25) + 1)
    cap = 256
    while cap < want:
        cap *= 2
    return cap


def mark_bytes(mirror_bytes: float, rows: int) -> float:
    """Bytes one victim mark must move: every stored column of the mirror
    read once (``kb_mirror_bytes``: keys, revision halves, the tombstone
    and TTL flag columns, each at the padded width) and one mask byte
    written a padded row."""
    return mirror_bytes + padded_capacity(rows)


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.get("scrapes"):
        return None
    hits = [(sec, n) for name, sec, n in tr["ops"] if KERNEL.match(name)]
    seconds, calls = sum(h[0] for h in hits), sum(h[1] for h in hits)
    if seconds <= 0:
        return None
    mirror_bytes = prom.series_sum(tr["scrapes"][-1], "kb_mirror_bytes")
    need = calls * mark_bytes(mirror_bytes, ctx.mirror_rows)
    peak = roofline.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / seconds
