#!/usr/bin/env python3
"""The server under test, started as the README starts it — this file calls
``kubebrain_tpu.cli.main`` with the configuration's own arguments and touches
nothing of it — plus one side thread that only the process holding the chip
can provide: the device as JAX reports it, its peak memory, and a profiler
capture written where the benchmark says (the program's ``/debug/profile``
writes to a fixed ``/tmp/kb-jax-profile-<t>``, which the benchmark's
contract forbids, and nothing of the program reports device memory;
PERF.md section 7).

    python benchmarks/serve_child.py <probe-port> <cli arguments...>

Probe (HTTP on 127.0.0.1:<probe-port>):
    /device                      platform, kind, count, memory_peak_bytes
    /profile/start?dir=D         start one jax.profiler capture into D
    /profile/stop                stop it; start and stop times (monotonic)
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

_capture = threading.Lock()


def _device() -> dict:
    import jax

    devices = jax.local_devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


_started: dict = {}


def _profile_start(out_dir: str) -> dict:
    import jax

    if not _capture.acquire(blocking=False):
        return {"error": "a capture is running"}
    t0 = time.monotonic()
    try:
        # the Python tracer hooks every call of this (Python) server and
        # slows the very window it traces; device ops and TraceMe spans stay
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(out_dir, profiler_options=options)
    except BaseException:
        _capture.release()
        raise
    start = time.monotonic()
    _started.update(dir=out_dir, start=start, init_s=start - t0)
    return dict(_started)


def _profile_stop() -> dict:
    import jax

    if not _started:
        return {"error": "no capture is running"}
    try:
        stop = time.monotonic()
        jax.profiler.stop_trace()
        return dict(_started, stop=stop, flush_s=time.monotonic() - stop)
    finally:
        _started.clear()
        _capture.release()


class _Probe(BaseHTTPRequestHandler):
    def do_GET(self):  # noqa: N802 (http.server's name)
        url = urllib.parse.urlparse(self.path)
        query = dict(urllib.parse.parse_qsl(url.query))
        try:
            if url.path == "/device":
                body = _device()
            elif url.path == "/profile/start":
                body = _profile_start(query["dir"])
            elif url.path == "/profile/stop":
                body = _profile_stop()
            else:
                self.send_error(404)
                return
        except Exception as e:  # the probe must not take the server down
            body = {"error": f"{type(e).__name__}: {e}"}
        raw = json.dumps(body).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(raw)))
        self.end_headers()
        self.wfile.write(raw)

    def log_message(self, *args):
        pass


def main(argv: list[str]) -> int:
    probe_port, cli_args = int(argv[0]), argv[1:]
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    httpd = ThreadingHTTPServer(("127.0.0.1", probe_port), _Probe)
    threading.Thread(target=httpd.serve_forever, name="bench-probe",
                     daemon=True).start()
    from kubebrain_tpu.cli import main as cli_main

    return cli_main(cli_args)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
