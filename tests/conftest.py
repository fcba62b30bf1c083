"""Test environment: force an 8-device virtual CPU mesh before any kernel runs.

Mirrors SURVEY §4's implication: mesh-sharded scans are tested on CPU via
``xla_force_host_platform_device_count`` (the role the in-process mock TiKV
cluster plays in the reference tests, backend_test.go:171-178).

Tests never touch an accelerator: the platform is pinned to the CPU here,
before the first backend initialization, whatever the environment says (a
chip belongs to one process at a time, and a test session is not it).
``python chip_smoke.py`` is the on-chip check.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# persistent compile cache: kernel shapes repeat across test runs
from kubebrain_tpu.util.jaxcache import use_compile_cache  # noqa: E402

use_compile_cache()

# hang self-diagnosis: if a run wedges (shared CI box, subprocess tests),
# dump every thread's stack after 8 minutes so the stall is attributable
import faulthandler  # noqa: E402

faulthandler.dump_traceback_later(480, repeat=True)

# ---------------------------------------------------------------------------
# Per-test hard deadline (VERDICT r3 weak #4 / next #8): a wedged test —
# typically a multi-process one blocked on a dead kbstored/kbfront handoff —
# must become a RED test with a stack trace, not a silent multi-minute CI
# hang. SIGALRM fires in the main thread (where pytest runs the test), dumps
# every thread's stack straight to the unbuffered real stderr (pytest's
# captured stderr is block-buffered and loses the dump on kill), reaps any
# child processes the test left wedged, and raises into the test.
# Override per test with @pytest.mark.deadline(seconds); 0 disables.

import signal  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# KB_SANITIZE=1: the umbrella switch — arms all three runtime sanitizers
# (lockcheck + fieldcheck + leakcheck) at once; KB_SANITIZE_STRICT=1 makes
# every one of them fail the offending test. The chaos suite
# (tests/test_faults.py) runs under this umbrella in CI.

_SANITIZE = os.environ.get("KB_SANITIZE") == "1"
_SANITIZE_STRICT = os.environ.get("KB_SANITIZE_STRICT") == "1"

# ---------------------------------------------------------------------------
# Opt-in lock-order race detector (see kubebrain_tpu/util/lockcheck.py and
# docs/static_analysis.md). KB_LOCKCHECK=1 wraps every project-created
# threading.Lock/RLock to build the runtime lock-order graph; a test that
# produces an ABBA inversion or holds a lock across a blocking call FAILS
# with the offending stacks. Installed here, before any test module imports
# kubebrain_tpu, so module-level locks are wrapped too.

_LOCKCHECK = os.environ.get("KB_LOCKCHECK") == "1" or _SANITIZE
if _LOCKCHECK:
    from kubebrain_tpu.util import lockcheck as _lockcheck

    _lockcheck.install()

# ---------------------------------------------------------------------------
# Opt-in field-write sanitizer (see kubebrain_tpu/util/fieldcheck.py and
# docs/static_analysis.md). KB_FIELDCHECK=1 instruments the @fieldcheck.track
# serving-path classes to record (class, field, thread, locks-held) on every
# attribute write; KB_FIELDCHECK_EXPORT=<path> dumps the observed guard sets
# at session end for kblint's --field-guards cross-check (the KB120 runtime
# twin). Observe-only by default; KB_FIELDCHECK_STRICT=1 additionally FAILS
# any test that produced a multi-thread no-common-guard write.

_FIELDCHECK = os.environ.get("KB_FIELDCHECK") == "1" or _SANITIZE
_FIELDCHECK_STRICT = (os.environ.get("KB_FIELDCHECK_STRICT") == "1"
                      or _SANITIZE_STRICT)
if _FIELDCHECK:
    from kubebrain_tpu.util import fieldcheck as _fieldcheck

    _fieldcheck.install()  # installs lockcheck too (guard observation)

# ---------------------------------------------------------------------------
# Opt-in linear-resource leak sanitizer (see kubebrain_tpu/util/leakcheck.py
# and docs/static_analysis.md). KB_LEAKCHECK=1 wraps the four linear-resource
# protocols the static KB123–KB126 rules track (dealt revisions, sched
# slots, watcher registrations, spans) and records acquire/release balance;
# KB_LEAKCHECK_EXPORT=<path> dumps the balances at session end for kblint's
# --leak-report cross-check. Observe-only by default; KB_LEAKCHECK_STRICT=1
# additionally FAILS any test that produced a leak violation.

_LEAKCHECK = os.environ.get("KB_LEAKCHECK") == "1" or _SANITIZE
_LEAKCHECK_STRICT = (os.environ.get("KB_LEAKCHECK_STRICT") == "1"
                     or _SANITIZE_STRICT)
if _LEAKCHECK:
    from kubebrain_tpu.util import leakcheck as _leakcheck

    _leakcheck.install()


@pytest.fixture(autouse=True)
def _leakcheck_guard():
    if not _LEAKCHECK:
        yield
        return
    _leakcheck.take_violations()  # stale noise from other tests' threads
    yield
    _leakcheck.check_teardown()   # sweep close-less resources (spans)
    found = _leakcheck.take_violations()
    if found and _LEAKCHECK_STRICT:
        raise _leakcheck.LeakError(
            "linear-resource leaks during this test:\n"
            + "\n".join(v.render() for v in found)
        )


@pytest.fixture(autouse=True)
def _fieldcheck_guard():
    if not (_FIELDCHECK and _FIELDCHECK_STRICT):
        yield
        return
    _fieldcheck.take_violations()  # stale noise from other tests' threads
    yield
    found = _fieldcheck.take_violations()
    if found:
        raise _fieldcheck.FieldRaceError(
            "racy field writes during this test:\n"
            + "\n".join(v.render() for v in found)
        )


@pytest.fixture(autouse=True)
def _lockcheck_guard():
    if not _LOCKCHECK:
        yield
        return
    _lockcheck.take_violations()  # stale noise from other tests' threads
    yield
    found = _lockcheck.take_violations()
    if found:
        raise _lockcheck.LockOrderError(
            "lock-discipline violations during this test:\n"
            + "\n".join(v.render() for v in found)
        )


def pytest_sessionfinish(session, exitstatus):
    # KB_LOCKCHECK_EDGES=<path>: dump the session's observed lock-order
    # graph for the static linter's KB115 cross-check (the runtime
    # detector's coverage gap becomes measurable:
    # python -m tools.kblint --deep --lock-edges <path> --lock-graph).
    edges_path = os.environ.get("KB_LOCKCHECK_EDGES")
    if _LOCKCHECK and edges_path:
        try:
            n = _lockcheck.export_edges(edges_path)
            sys.stderr.write(
                f"[lockcheck] exported {n} lock-order edges to {edges_path}\n")
        except OSError as e:
            sys.stderr.write(f"[lockcheck] edge export failed: {e}\n")
    # KB_FIELDCHECK_EXPORT=<path>: dump observed field guard sets for the
    # static linter's KB120 cross-check
    # (python -m tools.kblint --deep --field-observed <path> --field-guards)
    fields_path = os.environ.get("KB_FIELDCHECK_EXPORT")
    if _FIELDCHECK and fields_path:
        try:
            n = _fieldcheck.export_observed(fields_path)
            sys.stderr.write(
                f"[fieldcheck] exported {n} observed fields to "
                f"{fields_path}\n")
        except OSError as e:
            sys.stderr.write(f"[fieldcheck] field export failed: {e}\n")
    # KB_LEAKCHECK_EXPORT=<path>: dump the session's acquire/release
    # balances for the static linter's KB123–KB126 cross-check
    # (python -m tools.kblint --deep --leak-observed <path> --leak-report)
    leaks_path = os.environ.get("KB_LEAKCHECK_EXPORT")
    if _LEAKCHECK and leaks_path:
        try:
            n = _leakcheck.export_observed(leaks_path)
            sys.stderr.write(
                f"[leakcheck] exported {n} protocol kinds to "
                f"{leaks_path}\n")
        except OSError as e:
            sys.stderr.write(f"[leakcheck] export failed: {e}\n")


_DEADLINE_DEFAULT = 240.0


class TestDeadlineError(Exception):
    """The test exceeded its hard deadline (see conftest watchdog)."""


def _descendants(pid):
    """All descendant PIDs of `pid` via /proc (no psutil in this image)."""
    children = {}
    try:
        for entry in os.listdir("/proc"):
            if not entry.isdigit():
                continue
            try:
                with open(f"/proc/{entry}/stat", "rb") as f:
                    parts = f.read().split(b")")[-1].split()
                children.setdefault(int(parts[1]), []).append(int(entry))
            except OSError:
                continue
    except OSError:
        return []
    out, queue = [], [pid]
    while queue:
        for c in children.get(queue.pop(), ()):
            out.append(c)
            queue.append(c)
    return out


def _deadline_for(item):
    m = item.get_closest_marker("deadline")
    if m is not None and m.args:
        return float(m.args[0])
    return _DEADLINE_DEFAULT


def _phase_guard(item, phase):
    deadline = _deadline_for(item)
    if deadline <= 0:
        yield
        return
    # Only processes spawned DURING the wedged phase are reaped: killing all
    # descendants would take down module/session-scoped fixture servers
    # (kbstored/kbfront) shared by the rest of the module and bury the real
    # failure under cascading connection errors. Setup is exempt entirely —
    # a module-scoped server fixture can start INSIDE this test's setup
    # phase and must survive for the rest of the module, so a setup timeout
    # only dumps stacks and raises (any child the wedged fixture spawned is
    # left to session teardown).
    reap = phase != "setup"
    preexisting = set(_descendants(os.getpid())) if reap else set()

    def on_alarm(signum, frame):
        sys.__stderr__.write(
            f"\n[deadline] test {item.nodeid} exceeded {deadline:.0f}s "
            f"in {phase}; dumping stacks\n"
        )
        faulthandler.dump_traceback(file=sys.__stderr__)
        kids = []
        if reap:
            kids = [k for k in _descendants(os.getpid()) if k not in preexisting]
            for k in kids:
                try:
                    os.kill(k, signal.SIGKILL)
                except OSError:
                    pass
            if kids:
                sys.__stderr__.write(f"[deadline] SIGKILLed children: {kids}\n")
        sys.__stderr__.flush()
        raise TestDeadlineError(
            f"{item.nodeid}: exceeded {deadline:.0f}s deadline during {phase} "
            f"(stacks on stderr; {len(kids)} child process(es) reaped)"
        )

    old_handler = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, deadline)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old_handler)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    yield from _phase_guard(item, "setup")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    yield from _phase_guard(item, "call")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    yield from _phase_guard(item, "teardown")
