"""One account per request that adds up, from inside the program: the stage
histogram by RPC kind, the unaccounted remainder, the TPU engine's
``delta_overlay`` stage, the delta merge by phase with the writers' lock
wait, the delta's fill, boot by phase, and the host's stages on the
profiler's clock (``kb.*`` annotations)."""

import contextlib
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import pytest

from kubebrain_tpu.cli import BootPhases, boot_line, build_endpoint, build_parser
from kubebrain_tpu.metrics import Metrics
from kubebrain_tpu.metrics.prom import PrometheusMetrics
from kubebrain_tpu.proto import rpc_pb2
from kubebrain_tpu.trace import Tracer, _covered

from test_etcd_server import EtcdClient, free_port

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RANGE, TXN = "etcd.KV/Range", "etcd.KV/Txn"


def _bench_prom():
    """The benchmark's own ``/metrics`` reader (stdlib only): the label-
    subset sums the accepted metrics are made of."""
    spec = importlib.util.spec_from_file_location(
        "bench_prom", os.path.join(ROOT, "benchmarks", "prom.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


prom = _bench_prom()


def _scrape(metrics: PrometheusMetrics) -> dict:
    return prom.parse(metrics.http_handler()()[1].decode())


class _Recorder(Metrics):
    """Keeps every histogram observation as (name, value, tags)."""

    def __init__(self):
        self.seen = []

    def emit_counter(self, name, value=1, **tags):
        pass

    def emit_gauge(self, name, value, **tags):
        self.seen.append((name, value, tags))

    def emit_histogram(self, name, value, **tags):
        self.seen.append((name, value, tags))

    def of(self, name, **tags):
        return [v for n, v, t in self.seen if n == name
                and all(t.get(k) == w for k, w in tags.items())]


# ------------------------------------------------------- the stage histogram
def test_rpc_label_on_a_scrape_and_label_subset_sums_unchanged():
    """``rpc`` splits the stage histogram by kind of RPC; a reader that
    names only ``stage`` (every metric the benchmark had) sums over the
    kinds and reads what it read before the label existed."""
    m = PrometheusMetrics()
    t = Tracer(metrics=m, slow_ms=0.0)
    before = _scrape(m)
    for _ in range(2):
        with t.span(RANGE) as sp:
            t.record_stage("device_dispatch", sp.t0, sp.t0 + 0.010)
            t.record_stage("host_copy", sp.t0 + 0.010, sp.t0 + 0.040)
    with t.span(TXN) as sp:
        t.record_stage("queue_wait", sp.t0, sp.t0 + 0.002)
        t.record_stage("host_copy", sp.t0 + 0.002, sp.t0 + 0.004)
    t.record_stage("host_copy", 0.0, 0.001)  # spanless: rpc=""
    after = _scrape(m)

    def count(**labels):
        return prom.delta(after, before, "kb_rpc_stage_seconds_count", **labels)

    assert count(stage="host_copy", rpc=RANGE) == 2
    assert count(stage="host_copy", rpc=TXN) == 1
    assert count(stage="host_copy", rpc="") == 1
    # the label subset: what host_copy_ms and scan_wait_ms read
    assert count(stage="host_copy") == 4
    assert prom.mean_delta(after, before, "kb_rpc_stage_seconds",
                           stage="host_copy") == pytest.approx(
        (0.030 * 2 + 0.002 + 0.001) / 4)
    # the dispatch count of read_roofline_pct and check.device_account
    assert count(stage="device_dispatch") == 2
    assert prom.mean_delta(after, before, "kb_rpc_stage_seconds",
                           stage="queue_wait", rpc=RANGE) is None
    labels = {tuple(sorted(lb)) for lb, _v in after["kb_rpc_stage_seconds_count"]}
    assert labels == {("rpc", "stage")}  # every emitter passes both


def test_unaccounted_is_the_gap_the_stages_leave():
    rec = _Recorder()
    t = Tracer(metrics=rec, slow_ms=0.0)
    with t.span("tiled"):
        with t.stage("a"):
            time.sleep(0.005)
        with t.stage("b"):
            time.sleep(0.005)
    with t.span("holed"):
        with t.stage("a"):
            time.sleep(0.005)
        time.sleep(0.02)  # nobody's
        with t.stage("b"):
            time.sleep(0.005)
    (tiled,) = rec.of("kb.rpc.unaccounted.seconds", rpc="tiled")
    (holed,) = rec.of("kb.rpc.unaccounted.seconds", rpc="holed")
    assert 0.0 <= tiled < 0.002
    assert 0.019 <= holed < 0.5  # a loaded box oversleeps


def test_covered_counts_overlapping_stages_once():
    # backend_write wraps the write's queue_wait; a stage may outlive its span
    stages = [("backend_write", 0.0, 0.010), ("queue_wait", 0.002, 0.004),
              ("late", 0.015, 0.100)]
    assert _covered(stages, 0.020) == pytest.approx(0.015)
    assert _covered([], 0.5) == 0.0


def test_a_stage_entered_twice_is_one_observation_per_rpc():
    rec = _Recorder()
    t = Tracer(metrics=rec, slow_ms=0.0)
    with t.span(RANGE) as sp:
        t.record_stage("delta_overlay", sp.t0, sp.t0 + 0.001)
        t.record_stage("device_compute", sp.t0 + 0.001, sp.t0 + 0.002)
        t.record_stage("delta_overlay", sp.t0 + 0.002, sp.t0 + 0.005)
    assert rec.of("kb.rpc.stage.seconds", stage="delta_overlay",
                  rpc=RANGE) == [pytest.approx(0.004)]
    # the ring keeps both intervals
    names = [s["stage"] for s in t.snapshot()["traces"][-1]["stages"]]
    assert names.count("delta_overlay") == 2


# ---------------------------------------------------------- the profiler sink
def test_stage_annotates_what_a_thread_does_and_only_that():
    entered = []

    @contextlib.contextmanager
    def annotate(name):
        entered.append(name)
        yield

    t = Tracer()
    with t.span("x"):
        with t.stage("host_copy"):  # no sink: works the same
            pass
    t.set_annotator(annotate)
    with t.span("x"):
        with t.stage("host_copy"):
            pass
        t.record_stage("queue_wait", 0.0, 1.0)  # a wait: nobody does it
    with t.annotate("merge.build"):  # background work, no span, no stage
        pass
    assert entered == ["kb.host_copy", "kb.merge.build"]
    assert [s["stage"] for s in t.snapshot()["traces"][-1]["stages"]] == [
        "host_copy", "queue_wait"]
    t.set_annotator(None)
    with t.annotate("merge.build"):
        pass
    assert len(entered) == 2


def test_trace_package_imports_no_jax():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, kubebrain_tpu.trace; print('jax' in sys.modules)"],
        cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert out.stdout.strip() == "False", out.stderr[-2000:]


# -------------------------------------------------------------- boot by phase
def test_boot_phases_tile_the_clock_and_set_each_gauge_once():
    rec = _Recorder()
    t0 = time.monotonic()
    boot = BootPhases(t0, rec)
    time.sleep(0.01)
    boot.mark("jax_init")
    time.sleep(0.005)
    boot.mark("store_open")
    boot.mark("listen")
    assert list(boot.seconds) == ["jax_init", "store_open", "listen"]
    assert sum(boot.seconds.values()) == pytest.approx(
        time.monotonic() - t0, abs=0.05)
    assert boot.seconds["jax_init"] >= 0.01
    assert [t["phase"] for n, _v, t in rec.seen if n == "kb.boot.seconds"] == [
        "jax_init", "store_open", "listen"]


# ------------------------------------------- a served TPU engine (CPU backend)
@pytest.fixture(scope="module")
def tpu_server():
    port, info_port = free_port(), free_port()
    args = build_parser().parse_args([
        "--single-node", "--storage", "tpu", "--inner-storage", "memkv",
        "--host", "127.0.0.1", "--client-port", str(port),
        "--peer-port", str(free_port()), "--info-port", str(info_port),
        "--trace-slow-ms", "0", "--merge-threshold", "100000",
    ])
    endpoint, backend, store = build_endpoint(args)
    endpoint.run()
    client = EtcdClient(f"127.0.0.1:{port}")
    for i in range(60):
        client.create(b"/registry/pods/ns-%d/pod-%04d" % (i % 3, i), b"x" * 64)
    _list(client)  # the first read builds the mirror (boot's mirror_build)
    yield client, info_port, backend, endpoint
    client.close()
    endpoint.close()
    backend.close()
    store.close()


def _list(client, limit=0, count_only=False):
    return client.range_(rpc_pb2.RangeRequest(
        key=b"/registry/pods/", range_end=b"/registry/pods0", limit=limit,
        count_only=count_only))


def _http(info_port, path, timeout=60):
    with urllib.request.urlopen(f"http://127.0.0.1:{info_port}{path}",
                                timeout=timeout) as resp:
        return resp.read().decode()


def _metrics(info_port) -> dict:
    return prom.parse(_http(info_port, "/metrics"))


def _last_span(info_port, name=RANGE) -> dict:
    traces = json.loads(_http(info_port, "/debug/traces?"))["traces"]
    return [t for t in traces if t["name"] == name][-1]


def _unaccounted_ms(span: dict) -> float:
    stages = [(s["stage"], s["offset_ms"], s["duration_ms"])
              for s in span["stages"]]
    return span["duration_ms"] - _covered(stages, span["duration_ms"])


@pytest.mark.parametrize("count_only", [False, True], ids=["range", "count"])
def test_tpu_read_records_delta_overlay_and_accounts_for_itself(tpu_server,
                                                                count_only):
    client, info_port, _backend, _ep = tpu_server
    client.create(b"/registry/pods/ns-0/fresh-%d" % count_only, b"y")  # a delta row
    _list(client, count_only=count_only)  # warm: this shape's compile
    before = _metrics(info_port)
    best, spans = 1e9, []
    for _ in range(5):
        resp = _list(client, count_only=count_only)
        spans.append(_last_span(info_port))
        best = min(best, _unaccounted_ms(spans[-1]))
    assert resp.count >= 61
    names = [s["stage"] for s in spans[-1]["stages"]]
    assert {"endpoint_recv", "queue_wait", "delta_overlay", "device_dispatch",
            "device_compute", "result_deliver", "response_encode"} <= set(names)
    assert "host_scan" not in names
    # a Count corrects its total by the overlay after the kernel: the stage
    # again, yet one observation per RPC on /metrics
    assert names.count("delta_overlay") == (2 if count_only else 1)
    assert ("host_copy" in names) != count_only
    assert best < 0.5, [_unaccounted_ms(s) for s in spans]
    after = _metrics(info_port)
    for stage in ("delta_overlay", "device_dispatch"):
        assert prom.delta(after, before, "kb_rpc_stage_seconds_count",
                          stage=stage, rpc=RANGE) == 5
    assert prom.delta(after, before, "kb_rpc_unaccounted_seconds_count",
                      rpc=RANGE) == 5


def test_a_wire_range_tiles_its_span_and_counts_its_reply_path(tpu_server):
    """An unpaged default-sort Range leaves the mirror as wire bytes
    (``TpuScanner.list_wire``): the stages a rows Range records, every one
    still observed — ``host_copy`` around the gather, ``response_encode``
    around the scalar header — one ``device_dispatch`` a dispatch, and
    ``kb_range_reply_total`` says which path a list took."""
    client, info_port, _backend, _ep = tpu_server
    _list(client)  # warm
    before = _metrics(info_port)
    best = 1e9
    for _ in range(4):
        wire = _list(client)
        span = _last_span(info_port)
        best = min(best, _unaccounted_ms(span))
    names = [s["stage"] for s in span["stages"]]
    assert names == ["endpoint_recv", "queue_wait", "delta_overlay",
                     "device_dispatch", "device_compute", "host_copy",
                     "result_deliver", "response_encode"]
    assert best < 0.5
    rows = client.range_(rpc_pb2.RangeRequest(
        key=b"/registry/pods/", range_end=b"/registry/pods0", keys_only=True))
    assert [kv.key for kv in rows.kvs] == [kv.key for kv in wire.kvs]
    assert wire.count == rows.count == len(wire.kvs) and not wire.more
    page = _list(client, limit=5)  # a small page: the host path, still wire
    assert [kv.key for kv in page.kvs] == [kv.key for kv in wire.kvs[:5]]
    after = _metrics(info_port)

    def moved(name, **labels):
        return prom.delta(after, before, name, **labels)

    assert moved("kb_range_reply_total", path="wire") == 5
    assert moved("kb_range_reply_total", path="rows") == 1
    assert {lb["path"] for lb, _v in after["kb_range_reply_total"]} == {
        "wire", "rows"}
    for stage, n in (("host_copy", 6), ("response_encode", 6),
                     ("device_dispatch", 5), ("device_compute", 5),
                     ("host_scan", 1)):
        assert moved("kb_rpc_stage_seconds_count", stage=stage, rpc=RANGE) == n
    # no device read without its dispatch: what check.device_account holds
    assert moved("kb_sched_batch_size_sum") - moved("kb_sched_batch_size_count") == 0


def test_small_page_is_the_host_scanners_and_says_so(tpu_server):
    client, info_port, _backend, _ep = tpu_server
    resp = _list(client, limit=5)  # under the host-limit threshold
    assert len(resp.kvs) == 5 and resp.more
    names = {s["stage"] for s in _last_span(info_port)["stages"]}
    assert "host_scan" in names
    assert not names & {"device_dispatch", "device_compute", "delta_overlay"}


def test_a_merge_emits_each_phase_once_beside_the_whole(tpu_server):
    client, info_port, backend, _ep = tpu_server
    for i in range(7):
        client.create(b"/registry/pods/ns-1/merged-%d" % i, b"z")
    before = _metrics(info_port)
    assert prom.series_sum(before, "kb_mirror_delta_rows") >= 7
    backend.scanner.publish()  # one delta merge, on this thread
    after = _metrics(info_port)
    assert prom.delta(after, before, "kb_mirror_merge_seconds_count") == 1
    phases = 0.0
    for phase in ("snapshot", "build", "swap"):
        assert prom.delta(after, before, "kb_mirror_merge_phase_seconds_count",
                          phase=phase) == 1
        phases += prom.delta(after, before, "kb_mirror_merge_phase_seconds_sum",
                             phase=phase)
    # the phases tile the merge: their sum IS kb_mirror_merge_seconds
    assert phases == pytest.approx(
        prom.delta(after, before, "kb_mirror_merge_seconds_sum"), abs=1e-6)
    assert any(lb.get("who") == "merge"
               for lb, _v in after["kb_mirror_lock_wait_seconds_total"])
    # the gauge follows the writes, and the merge
    assert prom.series_sum(after, "kb_mirror_delta_rows") == 0
    client.create(b"/registry/pods/ns-1/after-merge", b"z")
    assert prom.series_sum(_metrics(info_port), "kb_mirror_delta_rows") == 1


def test_lock_wait_counts_what_a_writer_waited_for_mlock(tpu_server):
    client, info_port, backend, _ep = tpu_server
    client.create(b"/registry/pods/ns-2/unblocked", b"w")
    before = _metrics(info_port)
    writer = threading.Thread(
        target=client.create, args=(b"/registry/pods/ns-2/held-out", b"w"))
    with backend.scanner._mlock:  # what a merge's swap does to every write
        writer.start()
        time.sleep(0.25)
    writer.join(timeout=30)
    assert not writer.is_alive()
    waited = prom.delta(_metrics(info_port), before,
                        "kb_mirror_lock_wait_seconds", who="write")
    assert 0.2 <= waited < 5.0


def test_boot_gauges_and_device_memory_on_metrics(tpu_server):
    _client, info_port, backend, endpoint = tpu_server
    # the first mirror build starts the compaction's warm-up on a thread
    # of its own: its phase joins once that is over
    deadline = time.monotonic() + 120
    while backend.scanner.compact_warm_s is None:
        assert time.monotonic() < deadline, "no compaction warm-up"
        time.sleep(0.05)
    snap = _metrics(info_port)
    boot = {lb["phase"]: v for lb, v in snap["kb_boot_seconds"]}
    # listen is main()'s to close (the in-process fixture runs the endpoint
    # itself); the first read built the mirror
    assert set(boot) == {"jax_init", "store_open", "mirror_build",
                         "compact_warm"}
    assert boot["mirror_build"] == pytest.approx(
        backend.scanner.boot_mirror_build_s)
    assert boot["compact_warm"] == pytest.approx(
        backend.scanner.compact_warm_s)
    assert boot["store_open"] == pytest.approx(
        endpoint.boot.seconds["store_open"])
    line = json.loads(boot_line(backend, endpoint.boot.seconds).split(": ", 1)[1])
    assert set(line["boot_s"]) == {"jax_init", "store_open"}
    devices = {lb["device"] for lb, _v in snap["kb_device_memory_peak_bytes"]}
    assert len(devices) == 8  # conftest's virtual CPU devices


def test_capture_holds_the_stages_on_the_profilers_clock(tpu_server, tmp_path):
    """Inside a ``jax.profiler`` capture every stage a thread does is an
    event ``kb.<stage>`` of a host plane, and a merge's phases
    ``kb.merge.<phase>``; outside one the same calls record the same."""
    client, info_port, backend, _ep = tpu_server
    from jax.profiler import ProfileData

    quiet = _list(client)
    out_dir = str(tmp_path / "capture")
    started = json.loads(_http(
        info_port, "/debug/profile/start?dir=" + urllib.parse.quote(out_dir)))
    assert started["dir"] == out_dir and started["init_s"] >= 0
    again = json.loads(_http(info_port, "/debug/profile/start"))
    assert "error" in again  # one capture at a time
    traced = _list(client)
    client.create(b"/registry/pods/ns-0/in-capture", b"c")
    backend.scanner.publish()
    stopped = json.loads(_http(info_port, "/debug/profile/stop"))
    assert stopped["dir"] == out_dir
    assert stopped["stop"] > stopped["start"] == started["start"]
    assert stopped["flush_s"] >= 0
    assert "error" in json.loads(_http(info_port, "/debug/profile/stop"))
    assert [kv.key for kv in traced.kvs] == [kv.key for kv in quiet.kvs]

    paths = [os.path.join(base, f) for base, _d, files in os.walk(out_dir)
             for f in files if f.endswith(".xplane.pb")]
    assert len(paths) == 1
    names = set()
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    assert {"kb.delta_overlay", "kb.device_dispatch", "kb.device_compute",
            "kb.host_copy", "kb.response_encode", "kb.merge.snapshot",
            "kb.merge.build", "kb.merge.swap"} <= names
    assert not names & {"kb.queue_wait", "kb.result_deliver"}  # waits


def test_debug_profile_writes_where_it_is_told(tpu_server, tmp_path):
    _client, info_port, _backend, _ep = tpu_server
    out_dir = str(tmp_path / "timed")
    out = json.loads(_http(
        info_port, "/debug/profile?seconds=0.05&dir=" + urllib.parse.quote(out_dir)))
    assert out["dir"] == out_dir
    assert out["seconds"] == pytest.approx(0.05)
    assert out["stop"] - out["start"] >= 0.05
    assert any(f.endswith(".xplane.pb")
               for _b, _d, files in os.walk(out_dir) for f in files)
    with pytest.raises(urllib.error.HTTPError) as gone:  # the old alias
        _http(info_port, "/debug/jax-profile")
    assert gone.value.code == 404
