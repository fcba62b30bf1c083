"""The read path's share of the HBM roofline over the traced seconds: the
least time the chip could take for the bytes the traced seconds' read
dispatches cannot avoid (``roofline.read_bytes``, blind to which kernel
runs) at the device kind's peak bandwidth, over the seconds the device was
busy there. Bandwidth-bound: the scan compares and masks, it multiplies
nothing. Nothing where the trace or the dispatch count is missing."""

import prom
import roofline


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.get("scrapes") or tr["busy_s"] <= 0:
        return None
    before, after = tr["scrapes"]
    dispatches = prom.delta(after, before, "kb_rpc_stage_seconds_count",
                            stage="device_compute")
    if dispatches <= 0:
        return None
    riders = (prom.delta(after, before, "kb_sched_batch_size_sum")
              - prom.delta(after, before, "kb_sched_batch_size_count"))
    mirror_bytes = prom.series_sum(after, "kb_mirror_bytes")
    need = roofline.read_bytes(dispatches, dispatches + max(0.0, riders),
                               mirror_bytes, roofline.padded_rows(ctx.mirror_rows))
    peak = roofline.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / tr["busy_s"]
