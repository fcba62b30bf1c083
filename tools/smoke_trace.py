#!/usr/bin/env python
"""Observability smoke check (tools/ci.sh + check.yml): start a real server,
issue one Range through the client library, and assert the trace pipeline is
live end to end — /debug/traces holds a multi-stage Range span and
kb_rpc_stage_seconds shows queue-wait + device-compute on /metrics.

Exit 0 on success; prints the failing surface otherwise.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def main() -> int:
    sys.path.insert(0, REPO)
    from kubebrain_tpu.client import EtcdCompatClient

    client_port, info_port = free_port(), free_port()
    server = subprocess.Popen(
        [sys.executable, "-m", "kubebrain_tpu.cli", "--single-node",
         "--storage", "memkv", "--host", "127.0.0.1",
         "--client-port", str(client_port),
         "--peer-port", str(free_port()), "--info-port", str(info_port),
         "--jax-platform", "cpu"],
        cwd=REPO, stderr=subprocess.DEVNULL,
    )
    try:
        # fresh channel per probe: a channel opened before the server binds
        # accrues reconnect backoff and can stay TRANSIENT_FAILURE long
        # after the port is live (the test_kvrpc boot-probe lesson)
        c = None
        deadline = time.time() + 60
        while time.time() < deadline:
            probe = EtcdCompatClient(f"127.0.0.1:{client_port}")
            try:
                probe.count(b"/x", b"/y")
                c = probe
                break
            except Exception:
                probe.close()
                time.sleep(0.3)
        if c is None:
            print("FAIL: server never served", file=sys.stderr)
            return 1
        ok, _rev = c.create(b"/registry/pods/default/smoke-1", b"v1")
        assert ok, "create failed"
        kvs, _ = c.list(b"/registry/pods/", b"/registry/pods0")
        assert len(kvs) == 1, kvs
        c.close()

        with urllib.request.urlopen(
            f"http://127.0.0.1:{info_port}/debug/traces", timeout=10
        ) as resp:
            snap = json.loads(resp.read())
        ranges = [t for t in snap["traces"] if t["name"] == "etcd.KV/Range"]
        if not ranges:
            print(f"FAIL: no Range span in /debug/traces: {snap}", file=sys.stderr)
            return 1
        stages = {s["stage"] for s in ranges[-1]["stages"]}
        if len(stages) < 5 or not {"queue_wait", "host_scan"} <= stages:
            print(f"FAIL: Range span stages incomplete: {sorted(stages)}",
                  file=sys.stderr)
            return 1

        with urllib.request.urlopen(
            f"http://127.0.0.1:{info_port}/metrics", timeout=10
        ) as resp:
            metrics = resp.read().decode()
        for needle in ("kb_rpc_stage_seconds_bucket",
                       'stage="queue_wait"', 'stage="host_scan"'):
            if needle not in metrics:
                print(f"FAIL: {needle!r} missing from /metrics", file=sys.stderr)
                return 1
        print(f"OK: trace smoke — span stages {sorted(stages)}, "
              "kb_rpc_stage_seconds populated")
        return 0
    finally:
        server.terminate()
        try:
            server.wait(timeout=10)
        except subprocess.TimeoutExpired:
            server.kill()


if __name__ == "__main__":
    sys.exit(main())
