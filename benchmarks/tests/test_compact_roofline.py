"""The victim-mark kernel's roofline share (``readers/compact_roofline_pct``):
the padding rule it restates, the bytes a mark cannot avoid, and the reading
over a reduced trace."""

import os
import sys
from types import SimpleNamespace

import pytest

import plugin
import roofline
from conftest import BENCH

sys.path.insert(0, os.path.dirname(BENCH))
from kubebrain_tpu.storage.tpu.blocks import padded_capacity  # noqa: E402

reader = plugin.load(os.path.join(BENCH, "readers"), "compact_roofline_pct")


@pytest.mark.parametrize("rows", [0, 1, 204, 205, 77_500, 239_548, 253_048,
                                  419_430, 419_431])
def test_padding_is_the_mirrors(rows):
    assert reader.padded_capacity(rows) == padded_capacity(rows)


def test_bytes_of_one_mark():
    # 239,548 rows pad to 524,288; 34 stored bytes a row on the device
    assert reader.padded_capacity(239_548) == 524_288
    assert reader.mark_bytes(34 * 524_288, 239_548) == 35 * 524_288


def _ctx(ops, mirror_bytes=34 * 524_288.0):
    scrape = {"kb_mirror_bytes": [({"device": "TPU_0"}, mirror_bytes)]}
    return SimpleNamespace(
        trace={"ops": ops, "scrapes": [scrape, scrape], "busy_s": 7.0},
        mirror_rows=253_048, device={"kind": "TPU v5 lite"})


def test_reading_over_the_kernels_own_time():
    peak = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    ops = [["%victim_mask_batch_cached.1 = s8[1,1,524288]{2,1,0} custom-call(...)",
            0.002, 1],
           ["%convert_reduce_fusion = pred[1,524288] fusion(s8[1,1,524288] "
            "%victim_mask_batch_cached.1)", 0.5, 1],
           ["%fusion = s32[4096] fusion(s32[524288] %x)", 6.0, 500]]
    want = 100.0 * (35 * 524_288 / peak) / 0.002
    assert reader.read(_ctx(ops)) == pytest.approx(want)
    # the ledger's form of the same name
    ops[0][0] = "_victim_mask_batch_cached.1___s8_1_1_524288__2_1_0"
    assert reader.read(_ctx(ops)) == pytest.approx(want)


def test_nothing_without_the_kernel():
    assert reader.read(_ctx([["%fusion = s32[4096] fusion(...)", 6.0, 500]])) is None
    assert reader.read(SimpleNamespace(trace=None)) is None
