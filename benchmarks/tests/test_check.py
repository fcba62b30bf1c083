"""The control, kept as a test: the plain reference put in the program's
place reads correct, and with one guarantee broken it reads NOT correct —
and the second test the contract asks for: the rest of a run driven without
the look for a chip, the timed path broken underneath (an answer altered
where it is produced; a write acknowledged and never applied)."""

import json
import os
from types import SimpleNamespace

import pytest

import check
import etcd
import run
from conftest import BENCH
from state import State

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = {w["name"]: w for w in BENCHMARK["workloads"]}
with open(os.path.join(BENCH, "tests", "history_cell.json")) as _f:
    HISTORY_CELL = json.load(_f)


def _run(cell: str, broken: str, seed: int = 41):
    opts = SimpleNamespace(workload=cell, seed=seed, seconds=3.0, trace=0,
                           sut="reference", broken=broken, scale=0.01,
                           keep_trace="", chips=1)
    workload, config = run.cell_files(CELLS[cell])
    workload["warm_seconds"] = 1.0
    for stream in workload["streams"]:
        stream["sample_prob"] = 1.0   # few answers at this size: compare all
    return run.run_once(opts, workload, config, BENCHMARK)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_the_reference_in_the_programs_place_reads_correct(cell):
    out = _run(cell, "")
    assert check.verdict(out["numbers"]), out["numbers"]
    # a rehearsal is never a result
    assert out["result"]["correct"] is False


@pytest.mark.parametrize("cell,broken,caught_by", [
    ("k8s-2500.relist", "stale_read", "range_stale"),
    ("k8s-2500.relist", "altered_row", "range_rows_wrong"),
    ("k8s-2500.relist-merge", "stale_read", "range_stale"),
    ("k8s-2500.relist-merge", "altered_row", "range_rows_wrong"),
    ("k8s-2500.relist-merge", "lost_write", "writes_refused"),
    ("k8s-2500.steady", "stale_read", "readback_wrong"),
    ("k8s-2500.steady", "lost_write", "watch_wrong"),
    ("k8s-2500.steady", "dropped_event", "watch_wrong"),
    ("k8s-2500.steady", "altered_row", "readback_wrong"),
])
def test_a_broken_guarantee_reads_not_correct(cell, broken, caught_by):
    out = _run(cell, broken)
    n = out["numbers"][caught_by]
    assert n["value"] > n["limit"], out["numbers"]
    assert not check.verdict(out["numbers"])


# ---- a Compact: the two controls of the new comparisons
@pytest.mark.parametrize("seed", [2**31 + 101, 2**31 + 102, 2**31 + 103])
@pytest.mark.parametrize("broken,caught_by", [
    ("", None),
    ("uncompacted", "compacted_reads_not_refused"),    # no floor set
    ("over_compacted", "compact_readback_wrong"),      # a survivor dropped
])
def test_a_broken_compact_reads_not_correct(broken, caught_by, seed):
    """The plain reference in the program's place, on the configuration with
    a history and the mix with kube-apiserver's compactor: served whole it
    reads correct; a Compact acknowledged and never applied, or one that
    takes a survivor with its victims, reads not correct on every seed."""
    opts = SimpleNamespace(workload="tiny-history.compacting", seed=seed,
                           seconds=4.0, trace=0, sut="reference", broken=broken,
                           scale=1.0, keep_trace="", chips=1)
    workload = json.loads(json.dumps(HISTORY_CELL["traffic"]))
    out = run.run_once(opts, workload, json.loads(json.dumps(HISTORY_CELL["config"])),
                       BENCHMARK)
    n = out["numbers"]
    assert n["compared_compacts"]["value"] == 1 and n["compact_refused"]["value"] == 0
    assert n["compared_compact_readback"]["value"] >= 1
    assert n["compared_compacted_reads"]["value"] >= 1
    if not broken:
        assert check.verdict(n), n
        return
    assert n[caught_by]["value"] > n[caught_by]["limit"], n
    assert not check.verdict(n)


# ---- a failed write inside a sampled page (the fault of chip sets C and D)
def _page_case(applied: bool):
    """One namespace's first page of 5 rows, sampled, with a delete of its
    third key that FAILED at the client (a shed, a timeout): the server
    either never ran it (the key is still in the page) or did (it is not)."""
    config = run.load_json("configs", "k8s-2500.json")
    run.scale_tables(config, 0.01)
    state = State(config, 7)
    t = state.tables["pods"]
    live = [i for i in range(0, t.count, t.namespaces) if state.live["pods"][i]]
    row = lambda i: (t.key(i), int(state.rev["pods"][i]), state.value_crc(
        t, i, int(state.ver["pods"][i])))
    served = [i for i in live if not (applied and i == live[2])][:5]
    sample = {"sent": 1.0, "start": t.ns_prefix(0),
              "end": etcd.prefix_end(t.ns_prefix(0)), "limit": 5, "revision": 0,
              "header": state.head_revision, "more": True, "count": None,
              "rows": [row(i) for i in served]}
    failed = (check.TXN, "delete", 0.5, 0.5, 0.6, False, 0,
              state.key_id(t, live[2]), 0, 0, "RESOURCE_EXHAUSTED: shed", True)
    traffic = [{"recs": [failed], "samples": [sample], "judged": True,
                "loop": "open"}]
    return state, traffic, sample


@pytest.mark.parametrize("applied", [False, True])
def test_a_failed_write_inside_a_sampled_page_reads_correct(applied):
    state, traffic, sample = _page_case(applied)
    ref = check.Reference(state, traffic)
    n, message = check.sample_differs(ref, sample, ref.uncertain_keys())
    assert message == "" and n == 5 - (not applied), message
    # what chip sets C and D read: the limit re-applied to the reference's
    # rows AFTER the uncertain key was left out holds one row more than the
    # server's page without it ("4 rows, the reference holds 5")
    relimited = ref.rows(sample["start"], sample["end"], sample["header"])[:5]
    kept = [r for r in sample["rows"] if r[0] not in ref.uncertain_keys()]
    assert len(relimited) == 5 and len(kept) == 5 - (not applied)


def test_a_wrong_row_beside_a_failed_write_still_reads_not_correct():
    state, traffic, sample = _page_case(applied=False)
    del sample["rows"][0]          # the server lost a row it had acknowledged
    ref = check.Reference(state, traffic)
    _n, message = check.sample_differs(ref, sample, ref.uncertain_keys())
    assert "the reference holds" in message
    # and a short page that says there is more is no page either
    state, traffic, sample = _page_case(applied=False)
    sample["rows"].pop()
    ref = check.Reference(state, traffic)
    assert check.sample_differs(ref, sample, ref.uncertain_keys())[1]


# ---- the device account: right rows from the host path are not correct
@pytest.mark.parametrize("reads,dispatches,riders,coalesced,short", [
    (1450, 640, 800, 10, 0),     # every read dispatched, rode or joined
    (1450, 1, 0, 0, 1449),       # one dispatch among 1,450 reads
    (1450, 600, 800, 10, 40),    # forty reads went down the host iterator
    (5, 5, 0, 0, 0), (4, 0, 0, 0, 4)])
def test_device_reads_without_a_dispatch_are_counted(reads, dispatches, riders,
                                                     coalesced, short):
    account = {"dispatches": dispatches, "riders": riders, "coalesced": coalesced}
    assert check.undispatched(reads, account) == short


def test_the_account_reads_the_stage_only_the_kernel_path_records():
    import prom

    def scrape(dispatch, compute, members, batches, joined):
        return prom.parse(
            f'kb_rpc_stage_seconds_count{{stage="device_dispatch"}} {dispatch}\n'
            f'kb_rpc_stage_seconds_count{{stage="device_compute"}} {compute}\n'
            f'kb_sched_batch_size_sum {members}\nkb_sched_batch_size_count {batches}\n'
            f'kb_sched_coalesced_total{{lane="background"}} {joined}\n')
    # the host scanner records its iteration as device_compute: not counted
    assert check.device_account(scrape(10, 900, 30, 10, 4), scrape(4, 100, 10, 4, 1)) == {
        "dispatches": 6.0, "riders": 14.0, "coalesced": 3.0}


def test_where_the_mix_compacts_only_the_reads_dispatches_count():
    """A dispatch outside a read's span (``rpc=""``: a Compact's marking, or
    any background work that took the stage) cannot stand for a read that
    skipped the device: by ``rpc`` only the Range spans' count. Without the
    label filter (every cell that does not compact) the sum is the old one."""
    import prom

    def scrape(reads, other):
        return prom.parse(
            f'kb_rpc_stage_seconds_count{{stage="device_dispatch",rpc="etcd.KV/Range"}} {reads}\n'
            f'kb_rpc_stage_seconds_count{{stage="device_dispatch",rpc=""}} {other}\n'
            'kb_sched_batch_size_sum 0\nkb_sched_batch_size_count 0\n')
    later, earlier = scrape(40, 9), scrape(10, 1)
    assert check.device_account(later, earlier)["dispatches"] == 38.0
    assert check.device_account(later, earlier, check.READ_RPC)["dispatches"] == 30.0
    # 30 device reads, 30 dispatches of reads: none unmoved; with 8 of them
    # sent down the host path, the Compact's 8 dispatches do not cover them
    assert check.undispatched(30, check.device_account(later, earlier, check.READ_RPC)) == 0
    assert check.undispatched(38, check.device_account(later, earlier, check.READ_RPC)) == 8
    assert check.undispatched(38, check.device_account(later, earlier)) == 0


@pytest.mark.parametrize("cell", ["k8s-2500.relist", "k8s-2500.relist-merge"])
def test_the_program_with_its_device_path_off_reads_not_correct(cell):
    """The control of the device account, through the whole run on the CPU:
    the program's own host path (``--storage=native``) answers every Range
    byte for byte, and ``correct`` still comes out false."""
    opts = SimpleNamespace(workload=cell, seed=43, seconds=3.0,
                           trace=0, sut="hostpath", broken="", scale=0.04,
                           keep_trace="", chips=1)
    workload, config = run.cell_files(CELLS[cell])
    workload["warm_seconds"] = 1.0
    out = run.run_once(opts, workload, config, BENCHMARK)
    n = out["numbers"]
    assert n["range_rows_wrong"]["value"] == n["readback_wrong"]["value"] == 0
    assert n["device_reads_unmoved"]["value"] >= 0.5 * n["compared_device_reads"]["value"] > 0
    assert n["readback_device_unmoved"]["value"] == 4
    assert n["mirror_not_serving"]["value"] == 3
    assert not check.verdict(n) and out["result"]["correct"] is False
