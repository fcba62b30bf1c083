"""End-to-end request tracing: span trees with device-time attribution,
W3C traceparent propagation over the real gRPC surface, /debug/traces +
/debug/profile, the kb_rpc_stage_seconds histogram, watch-path lag
metrics, and auto pipeline depth (--sched-depth 0) from the measured
dispatch-RTT EWMA."""

import json
import urllib.request

import grpc
import pytest

from kubebrain_tpu.cli import build_endpoint, build_parser
from kubebrain_tpu.proto import rpc_pb2
from kubebrain_tpu.sched.scheduler import (
    AUTO_DEPTH_DEFAULT,
    AUTO_DEPTH_MAX,
    AUTO_DEPTH_MIN,
    RequestScheduler,
    SchedConfig,
)
from kubebrain_tpu.trace import (
    TRACER,
    Tracer,
    make_traceparent,
    parse_traceparent,
)

from test_etcd_server import EtcdClient, free_port


# ------------------------------------------------------------- traceparent
def test_traceparent_roundtrip():
    tp = make_traceparent()
    parsed = parse_traceparent(tp)
    assert parsed is not None
    trace_id, span_id = parsed
    assert len(trace_id) == 32 and len(span_id) == 16
    # bytes headers (grpc metadata values may be bytes) parse too
    assert parse_traceparent(tp.encode()) == parsed


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-zz-xx-01",
    "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # all-zero trace id
    "00-" + "1" * 32 + "-" + "0" * 16 + "-01",  # all-zero span id
])
def test_traceparent_rejects_invalid(bad):
    assert parse_traceparent(bad) is None


def test_traceparent_continues_ambient_trace():
    t = Tracer()
    with t.span("outer") as sp:
        tp = make_traceparent()
        assert parse_traceparent(tp)[0] == sp.trace_id


# ------------------------------------------------------- tracer mechanics
def test_span_ring_bounded_and_slow_log():
    t = Tracer(capacity=4, slow_ms=0.0)  # slow log off
    for i in range(10):
        with t.span(f"op-{i}"):
            pass
    snap = t.snapshot()
    assert len(snap["traces"]) == 4
    assert snap["traces"][-1]["name"] == "op-9"
    assert snap["slow"] == []

    slow = Tracer(capacity=4, slow_ms=0.001)  # everything is "slow"
    with slow.span("slowpoke"):
        with slow.stage("device_compute"):
            import time

            time.sleep(0.002)
    snap = slow.snapshot()
    assert [s["name"] for s in snap["slow"]] == ["slowpoke"]
    stages = snap["traces"][0]["stages"]
    assert stages[0]["stage"] == "device_compute"
    assert stages[0]["duration_ms"] >= 1.0


def test_span_records_error_and_nested_spans_collapse():
    t = Tracer()
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("nope")
    assert "ValueError" in t.snapshot()["traces"][-1]["error"]

    with t.span("outer") as outer:
        with t.span("inner") as inner:
            assert inner is outer  # one RPC = one span, terminals stack


def test_disabled_tracer_records_nothing():
    t = Tracer()
    t.enabled = False
    with t.span("ghost") as sp:
        assert sp is None
        with t.stage("device_compute"):
            pass
    assert t.snapshot()["traces"] == []
    # ...but EWMAs still update (auto-depth keeps working untraced)
    assert t.ewma("device_compute") is not None


def test_stage_ewma_and_dispatch_rtt():
    t = Tracer()
    assert t.dispatch_rtt() is None
    t.record_stage("device_dispatch", 0.0, 0.30)
    t.record_stage("device_compute", 0.0, 0.10)
    rtt = t.dispatch_rtt()
    assert rtt == pytest.approx(0.40)
    # EWMA converges toward repeated observations
    for _ in range(50):
        t.record_stage("device_compute", 0.0, 0.50)
    assert t.ewma("device_compute") == pytest.approx(0.50, rel=0.05)


def test_host_stages_do_not_feed_dispatch_rtt():
    """A host scan is ``host_scan``, a stage of its own: it must not shrink
    the auto-depth divisor, which only the device_* stages form."""
    t = Tracer()
    t.record_stage("host_scan", 0.0, 0.000005)  # µs host scan
    t.record_stage("host_copy", 0.0, 0.000001)
    assert t.dispatch_rtt() is None
    assert t.ewma("device_compute") is None
    t.record_stage("device_compute", 0.0, 0.02)
    assert t.dispatch_rtt() == pytest.approx(0.02)
    # the name-keyed EWMA (trace breakdowns) still sees the host scan
    assert t.ewma("host_scan") is not None


# ------------------------------------------------------------- auto depth
def test_auto_depth_adapts_to_synthetic_slow_dispatch():
    """--sched-depth 0: depth follows the tracer's dispatch-RTT EWMA —
    synthetic slow dispatch (long RTT vs short compute) widens the
    pipeline, clamped to [AUTO_DEPTH_MIN, AUTO_DEPTH_MAX]."""
    TRACER.reset()
    sched = RequestScheduler(None, SchedConfig(depth=0))
    try:
        # no measurements yet: the safe default
        assert sched.current_depth() == AUTO_DEPTH_DEFAULT

        def measured_dispatch(dispatch_s, compute_s):
            def fn():
                # synthetic device timings recorded through the real
                # execution path (worker thread, ambient span handling)
                TRACER.record_stage("device_dispatch", 0.0, dispatch_s)
                TRACER.record_stage("device_compute", 0.0, compute_s)
                return True

            return fn

        # dispatch RTT ~6x compute -> depth ceil((0.5+0.1)/0.1) = 6
        for _ in range(40):
            assert sched.submit(measured_dispatch(0.5, 0.1))
        assert sched.current_depth() == 6

        # dispatch collapses (local chips): depth shrinks to the floor
        for _ in range(80):
            assert sched.submit(measured_dispatch(0.0001, 0.1))
        assert sched.current_depth() == AUTO_DEPTH_MIN

        # pathological RTT: clamped at the ceiling
        for _ in range(80):
            assert sched.submit(measured_dispatch(30.0, 0.1))
        assert sched.current_depth() == AUTO_DEPTH_MAX
    finally:
        sched.close()
        TRACER.reset()


def test_fixed_depth_ignores_tracer():
    TRACER.reset()
    try:
        TRACER.record_stage("device_dispatch", 0.0, 30.0)
        TRACER.record_stage("device_compute", 0.0, 0.1)
        sched = RequestScheduler(None, SchedConfig(depth=3))
        assert sched.current_depth() == 3
        sched.close()
    finally:
        TRACER.reset()


def test_cli_accepts_sched_depth_zero():
    from kubebrain_tpu.cli import validate_args

    args = build_parser().parse_args(["--sched-depth", "0"])
    validate_args(args)  # must not raise
    with pytest.raises(SystemExit):
        validate_args(build_parser().parse_args(["--sched-depth", "-1"]))


# ------------------------------------------------------- wire end-to-end
@pytest.fixture(scope="module")
def server():
    port = free_port()
    info_port = free_port()
    args = build_parser().parse_args([
        "--single-node", "--storage", "memkv", "--host", "127.0.0.1",
        "--client-port", str(port),
        "--peer-port", str(free_port()), "--info-port", str(info_port),
        "--trace-slow-ms", "10000",
    ])
    endpoint, backend, store = build_endpoint(args)
    endpoint.run()
    client = EtcdClient(f"127.0.0.1:{port}")
    for i in range(40):
        client.create(b"/registry/pods/default/pod-%04d" % i, b"x" * 64)
    yield client, port, info_port
    client.close()
    endpoint.close()
    backend.close()
    store.close()


def _http_json(info_port, path, timeout=10):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{info_port}{path}", timeout=timeout
    ) as resp:
        return json.loads(resp.read())


def _http_text(info_port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{info_port}{path}", timeout=10
    ) as resp:
        return resp.read().decode()


def test_range_trace_stages_sum_to_latency(server):
    """Acceptance: a Range RPC through the real gRPC server yields a trace
    with >= 5 named stages whose durations sum to within 10% of the
    observed end-to-end latency, findable by the client's traceparent."""
    client, _port, info_port = server
    # warm the scheduler threads so queue_wait isn't dominated by startup
    for _ in range(3):
        client.range_(rpc_pb2.RangeRequest(
            key=b"/registry/pods/", range_end=b"/registry/pods0"))

    tp = make_traceparent()
    trace_id = parse_traceparent(tp)[0]
    client.range_(
        rpc_pb2.RangeRequest(key=b"/registry/pods/", range_end=b"/registry/pods0"),
        metadata=(("traceparent", tp),),
    )

    snap = _http_json(info_port, "/debug/traces")
    mine = [t for t in snap["traces"] if t["trace_id"] == trace_id]
    assert mine, f"trace {trace_id} not in /debug/traces"
    span = mine[0]
    assert span["name"] == "etcd.KV/Range"
    assert span["parent_id"] == parse_traceparent(tp)[1]
    stages = {s["stage"] for s in span["stages"]}
    assert len(stages) >= 5, span
    assert {"endpoint_recv", "queue_wait", "host_scan",
            "host_copy", "response_encode"} <= stages
    assert not stages & {"device_dispatch", "device_compute"}  # no device here
    total = sum(s["duration_ms"] for s in span["stages"])
    assert total == pytest.approx(span["duration_ms"], rel=0.10), span


def test_stage_histogram_on_metrics(server):
    """queue-wait and the host scan appear in kb_rpc_stage_seconds on
    /metrics (alongside the sched gauges + the new depth/RTT gauges)."""
    client, _port, info_port = server
    client.range_(rpc_pb2.RangeRequest(
        key=b"/registry/pods/", range_end=b"/registry/pods0"))
    body = _http_text(info_port, "/metrics")
    assert 'kb_rpc_stage_seconds_bucket{' in body
    assert 'stage="queue_wait"' in body
    assert 'stage="host_scan"' in body
    assert 'stage="device_compute"' not in body
    assert "kb_sched_depth" in body
    assert "kb_sched_dispatch_rtt_seconds" in body


def test_watch_lag_and_backlog_metrics(server):
    """Watch-path lag instrumentation: commit->delivery histogram and the
    per-watcher backlog gauge surface on /metrics."""
    client, _port, info_port = server
    import queue as _q

    requests: _q.Queue = _q.Queue()
    req = rpc_pb2.WatchRequest()
    req.create_request.key = b"/registry/pods/"
    req.create_request.range_end = b"/registry/pods0"
    requests.put(req)
    responses = client.watch(iter(requests.get, None))
    first = next(iter(responses))
    assert first.created
    client.create(b"/registry/pods/default/watched-1", b"v")
    got = next(iter(responses))
    assert got.events
    body = _http_text(info_port, "/metrics")
    assert 'kb_watch_lag_seconds_bucket{' in body
    assert 'point="queue"' in body
    assert 'point="wire"' in body
    assert 'kb_watch_backlog{watcher=' in body
    requests.put(None)
    # watcher death unregisters its backlog gauge eagerly (no scrape
    # needed in between — unregister_gauge_fn, not just scrape-time GC)
    import time as _time

    deadline = _time.monotonic() + 5
    while _time.monotonic() < deadline:
        if 'kb_watch_backlog{watcher=' not in _http_text(info_port, "/metrics"):
            break
        _time.sleep(0.1)
    else:
        pytest.fail("dead watcher's backlog gauge still registered")


def test_slow_request_log_via_wire(server):
    """A request slower than --trace-slow-ms lands in the slow log; this
    server's threshold is 10s so the log stays empty."""
    _client, _port, info_port = server
    snap = _http_json(info_port, "/debug/traces")
    assert snap["slow_ms"] == 10000
    assert snap["slow"] == []
    assert snap["stage_ewma_seconds"].get("host_scan") is not None


def test_debug_profile_on_demand(server):
    """/debug/profile?seconds=N captures a jax.profiler device trace."""
    _client, _port, info_port = server
    # the first start_trace of a process initializes the XLA profiler
    # plugin (~15s in this container); later captures take ~the capture time
    out = _http_json(info_port, "/debug/profile?seconds=0.05", timeout=90)
    assert "dir" in out, out
    assert out["seconds"] == pytest.approx(0.05)
    import os

    assert os.path.isdir(out["dir"])
    # malformed query answers with a JSON error, not a 500
    out = _http_json(info_port, "/debug/profile?seconds=bogus")
    assert "error" in out


def test_traceparent_metadata_flows_from_client_lib(server):
    """EtcdCompatClient injects traceparent on every call — server spans
    come out parented without the caller doing anything."""
    _client, port, info_port = server
    from kubebrain_tpu.client import EtcdCompatClient

    c = EtcdCompatClient(f"127.0.0.1:{port}")
    try:
        kvs, _rev = c.list(b"/registry/pods/", b"/registry/pods0")
        assert len(kvs) >= 40
    finally:
        c.close()
    snap = _http_json(info_port, "/debug/traces")
    parented = [t for t in snap["traces"]
                if t["parent_id"] is not None and t["name"] == "etcd.KV/Range"]
    assert parented, "client-lib Range produced no parented server span"


def test_coalesced_follower_records_join_stage():
    """Coalesced followers carry a coalesce_join stage; the execution
    stages live on the leader's span."""
    import threading
    import time as _time

    TRACER.reset()
    t = Tracer()
    sched = RequestScheduler(None, SchedConfig(depth=1))
    release = threading.Event()
    results = []

    try:
        # blocker occupies the single slot; decoy is the dispatcher's
        # in-hand request; leader stays queued (pending) so the keyed
        # follower can join it
        blocker = sched.submit_async(lambda: release.wait(5.0), client="a")
        _time.sleep(0.05)
        decoy = sched.submit_async(lambda: "decoy", client="b")
        leader = sched.submit_async(lambda: "lead", client="c", key="K")
        _time.sleep(0.05)

        def follower():
            with t.span("follower"):
                results.append(sched.submit(lambda: "never-runs", client="d",
                                            key="K"))

        th = threading.Thread(target=follower)
        th.start()
        _time.sleep(0.05)
        release.set()
        th.join(timeout=5)
        assert results == ["lead"]
        for r in (blocker, decoy, leader):
            r.wait(5.0)
        follower_span = t.snapshot()["traces"][-1]
        assert follower_span["name"] == "follower"
        stages = {s["stage"] for s in follower_span["stages"]}
        assert "coalesce_join" in stages
    finally:
        sched.close()
        TRACER.reset()
