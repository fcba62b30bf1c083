"""Per-test deadline enforcement (VERDICT r3 next #8).

Round 3's full suite wedged once with zero output until an outer 1200s
timeout killed it — a nonreproducible deadlock in the multi-process tests.
conftest.py now arms a SIGALRM watchdog around every test phase; this file
proves the enforcement end to end: a deliberately deadlocked test (blocked
forever on a sleeping child process) must FAIL in well under 120s with
thread stacks in the report and the wedged child reaped.
"""

import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

DEADLOCKED_TEST = '''
import subprocess
import sys

import pytest


@pytest.mark.deadline(6)
def test_blocks_forever_on_child():
    child = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(600)"])
    child.wait()  # never returns on its own — the watchdog must break it
'''


def test_deadlocked_subprocess_test_fails_fast(tmp_path):
    # run the deadlocked test under the real conftest watchdog
    (tmp_path / "conftest.py").write_text((REPO / "tests" / "conftest.py").read_text())
    (tmp_path / "test_wedge.py").write_text(DEADLOCKED_TEST)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path / "test_wedge.py"), "-q",
         "-p", "no:cacheprovider"],
        capture_output=True, text=True, timeout=115, cwd=str(tmp_path),
        # the conftest imports the package (compile-cache rule, sanitizers)
        env={**os.environ, "PYTHONPATH": str(REPO)},
    )
    elapsed = time.monotonic() - t0
    out = proc.stdout + proc.stderr
    assert proc.returncode != 0, out
    assert elapsed < 110, f"took {elapsed:.1f}s — watchdog did not fire"
    assert "TestDeadlineError" in out, out
    assert "exceeded 6s deadline" in out, out
    # the stack dump reached the report (real stderr, not the captured one)
    assert "Current thread" in out or "Thread 0x" in out, out
    # the wedged child was reaped
    assert "SIGKILLed children" in out, out


def test_normal_tests_unaffected():
    """The watchdog must be invisible to tests that finish in time."""
    assert True
