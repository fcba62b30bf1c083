"""The pause rule's two controls, on the CPU rehearsal of ``k8s-2500.steady``
(README.md, "The pause rule"). Each fails on every seed if the rule is wrong.
The rule judges nothing (every end-to-end metric is over every request);
the controls hold what its per-layer readings claim to be.

Both run the cell's mix at half its rate (``--sweep 0.5``: one JSON line with
the summary lines inside), so that the rehearsal's server, on a sandbox's
CPU, has the headroom over its open loop that the chip's host has over 270
txn/s, and drains a burst as fast.

(a) the MACHINE pauses: the run's whole process group (harness, server,
    generators) is stopped for 300 ms in mid-window. The witness sees one
    pause of about that length, the requests it touched leave the quiet
    tail, and the tail over every request shows the pause;
(b) the SERVER stalls: the server child alone is stopped for as long. The
    witness, which is neither server nor generator, sees nothing, no request
    is touched, and the quiet tail shows the stall as the tail over every
    request does: a server's stall is never laid to the machine.
"""

import json
import os
import re
import signal
import subprocess
import sys
import time
from collections import namedtuple

from conftest import BENCH

ROOT = os.path.dirname(BENCH)
STOP_S = 0.3
RATE = 0.5
TXN_LINE = re.compile(r"^txn: n=(\d+) \S+ p50=.*? p99=([\d.]+)ms .*?; touched by a "
                      r"pause (\d+); untouched .*? p99=([\d.]+)ms", re.M)
SimpleRun = namedtuple("SimpleRun", "n touched quiet_p99 all_p99 pauses total_ms max_ms text")
PAUSE_LINE = re.compile(r"^machine pauses: n=(\d+) total=([\d.]+) ms max=([\d.]+) ms", re.M)


def _children(pid: int) -> dict[int, str]:
    """pid -> command line of every child of ``pid``."""
    out = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
            with open(f"/proc/{entry}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
        except (OSError, ValueError, IndexError):
            continue
        if ppid == pid:
            out[int(entry)] = cmd
    return out


def _run_and_stop(tmp_path, seed: int, who: str) -> SimpleRun:
    """One 6 s rehearsal; 1.5 s into its window ``who`` ("group" or
    "server") is stopped for ``STOP_S``."""
    err_path, out_path = tmp_path / "err", tmp_path / "out"
    with open(err_path, "wb") as err, open(out_path, "wb") as out:
        proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
             "k8s-2500.steady", "--seed", str(seed), "--seconds", "6",
             "--trace", "0", "--sut", "cpu", "--scale", str(2000 / 77500),
             "--sweep", str(RATE)],
            cwd=ROOT, stdout=out, stderr=err, start_new_session=True)
        try:
            deadline = time.monotonic() + 300.0
            while b"window opens:" not in err_path.read_bytes():
                assert proc.poll() is None, err_path.read_text()[-3000:]
                assert time.monotonic() < deadline, "the window never opened"
                time.sleep(0.02)
            time.sleep(1.5)
            if who == "group":
                def send(sig):
                    os.killpg(proc.pid, sig)
            else:
                server, = [p for p, cmd in _children(proc.pid).items()
                           if "serve_child.py" in cmd]

                def send(sig):
                    os.kill(server, sig)
            send(signal.SIGSTOP)
            try:
                time.sleep(STOP_S)
            finally:
                send(signal.SIGCONT)
            assert proc.wait(timeout=300) == 0, err_path.read_text()[-3000:]
        finally:
            if proc.poll() is None:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
    line = json.loads(out_path.read_text().strip().splitlines()[-1])
    text = "\n".join(line["summary"])
    assert line["comparison_passed"] and line["failed"] == 0
    n, every, touched, quiet = TXN_LINE.search(text).groups()
    pauses, total_ms, max_ms = PAUSE_LINE.search(text).groups()
    assert set(line["metrics"]) == {"txn_p50_ms", "watch_lag_p50_ms", "setup_s"}
    assert line["attempted"] >= int(n)      # touched requests are still attempted
    return SimpleRun(int(n), int(touched), float(quiet), float(every),
                     int(pauses), float(total_ms), float(max_ms), text)


def _undisturbed(tmp_path, who: str, want_pauses: int) -> SimpleRun:
    """The run the control asks for, once more where the sandbox itself
    paused meanwhile (the witness says so: that is its job)."""
    for attempt in (1, 2):
        sub = tmp_path / str(attempt)
        sub.mkdir()
        r = _run_and_stop(sub, 2**31 + 29 + attempt, who)
        if r.pauses == want_pauses:
            break
    return r


def test_a_pause_of_the_machine_is_witnessed_and_leaves_the_quiet_tail(tmp_path):
    r = _undisturbed(tmp_path, "group", 1)
    # (the sandbox may add a pause of its own: the longest is the stop)
    assert r.pauses >= 1 and STOP_S * 1e3 - 5 <= r.max_ms < 2 * STOP_S * 1e3, r.text
    # the open loop's Txns were due all through the stop, and the burst behind it
    assert r.touched >= 0.9 * RATE * 270 * STOP_S, r.text
    assert r.quiet_p99 < STOP_S * 1e3 / 2 <= r.all_p99, r.text


def test_a_stall_of_the_server_alone_is_never_laid_to_the_machine(tmp_path):
    r = _undisturbed(tmp_path, "server", 0)
    # nothing like the stop was witnessed (a short pause of the sandbox's own
    # may have been), and the stall stays in the quiet tail
    assert r.max_ms < STOP_S * 1e3 / 2 <= r.quiet_p99 <= r.all_p99, r.text
    if r.pauses == 0:
        assert r.total_ms == 0.0 and r.touched == 0 and r.quiet_p99 == r.all_p99
