"""Multi-HOST data plane: 2 separate processes (2 virtual devices each) form
one 4-device global mesh via jax.distributed (Gloo collectives standing in
for ICI/DCN) and run the full sharded scan/compact/fan-out step — the
SURVEY §2.10 scale model executed for real, not just dry-run."""

import os
import re
import socket
import subprocess
import sys


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_global_mesh_step():
    port = str(free_port())
    worker = os.path.join(os.path.dirname(__file__), "mh_worker.py")
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(pid), "2", port],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        for pid in range(2)
    ]
    outputs = []
    for p in procs:
        out, _ = p.communicate(timeout=150)
        outputs.append(out.decode())
        assert p.returncode == 0, out.decode()[-2000:]
    totals = []
    for out in outputs:
        m = re.search(r"MHRESULT pid=(\d) devices=(\d+) total=(\d+)", out)
        assert m, out[-2000:]
        assert m.group(2) == "4"  # both processes see the global 4-device mesh
        totals.append(int(m.group(3)))
    assert totals[0] == totals[1] > 0  # psum agreed across hosts
