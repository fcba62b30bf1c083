"""kblint v4 (exception-path typestate / linear-resource leaks) self-tests:
KB123–KB126 on fixture programs, the CFG exception-edge construction they
ride on, the ownership-transfer policies (RacerD style: return / self-store
/ arg-pass / class-lifecycle), the unresolved-call honesty counters, the
leakcheck runtime sanitizer, and the static↔runtime --leak-observed
cross-check round trip.

The fixtures are dict-of-sources programs (relpath -> code) fed through
``deep_analyze_sources`` — same idiom as tests/test_kblint_races.py. Every
rule states the leaking variant AND its release-complete twin so the
detector is proven in both directions, plus the sanctioned handoff shapes
that must NOT fire (the scheduler's queue handoff, the runner's
stderr-handle transfer, notify-in-finally).
"""

import json
import os
import subprocess
import sys

import pytest

from tools.kblint import rules  # noqa: F401  -- registers the rules
from tools.kblint.core import deep_analyze_paths, deep_analyze_sources

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "kubebrain_tpu/x.py"

LEAK_RULES = {"KB123", "KB124", "KB125", "KB126"}


def deep(sources, **kw):
    return deep_analyze_sources(sources, **kw)


def leak_ids(sources, **kw):
    res = deep(sources, **kw)
    return [f.rule_id for f in res.findings if f.rule_id in LEAK_RULES]


# ------------------------------------------------------------------- KB123
# dealt-revision leak: every TSO.deal()/deal_block() result must reach
# _notify/_notify_many on every path or have its ownership transferred.

KB123_LEAKY = (
    "class Backend:\n"
    "    def __init__(self):\n"
    "        self.tso = TSO()\n"
    "    def commit(self, batch):\n"
    "        rev = self.tso.deal()\n"
    "        self._apply(batch)\n"        # may raise -> rev never notified
    "        self._notify(rev)\n"
    "    def _apply(self, batch):\n"
    "        pass\n"
    "    def _notify(self, rev):\n"
    "        pass\n"
)

KB123_CLEAN = (
    "class Backend:\n"
    "    def __init__(self):\n"
    "        self.tso = TSO()\n"
    "    def commit(self, batch):\n"
    "        rev = self.tso.deal()\n"
    "        try:\n"
    "            self._apply(batch)\n"
    "        finally:\n"
    "            self._notify(rev)\n"     # finally covers the exc edge too
    "    def _apply(self, batch):\n"
    "        pass\n"
    "    def _notify(self, rev):\n"
    "        pass\n"
)


def test_kb123_acceptance_pair_exception_edge():
    """THE KB123 acceptance pair: a storage call between deal and notify
    leaks the dealt revision on the exception edge; notify-in-finally
    (the real Backend.commit shape) is clean."""
    res = deep({PKG: KB123_LEAKY})
    assert [f.rule_id for f in res.findings] == ["KB123"]
    (f,) = res.findings
    assert f.line == 5                       # the deal() site
    assert "dealt revision rev" in f.message
    assert "exception edge" in f.message
    assert "_notify" in f.message
    assert "witness:" in f.message and "->" in f.message
    assert leak_ids({PKG: KB123_CLEAN}) == []


def test_kb123_normal_path_leak_and_deal_block():
    """KB123 demands discharge on ALL paths (unlike KB124/KB125): a
    deal_block() whose revision never reaches notify on the plain fall-
    through is flagged via a normal path."""
    src = (
        "class Backend:\n"
        "    def commit(self):\n"
        "        rev = self.tso.deal_block()\n"
        "        self.last = 1\n"
    )
    res = deep({PKG: src})
    assert [f.rule_id for f in res.findings] == ["KB123"]
    assert "normal path" in res.findings[0].message


def test_kb123_bare_discard_flagged_unbound():
    """`self.tso.deal()` discarding the revision outright is itself the
    leak — rendered as (unbound)."""
    src = (
        "class Backend:\n"
        "    def bump(self):\n"
        "        self.tso.deal()\n"
    )
    res = deep({PKG: src})
    assert [f.rule_id for f in res.findings] == ["KB123"]
    assert "(unbound)" in res.findings[0].message


def test_kb123_return_transfers_to_caller():
    """`return self.tso.deal()` hands the fresh revision to the caller —
    caller-side accounting owns it; no obligation here (the KB119 fixture
    interaction regression)."""
    src = (
        "class Replica:\n"
        "    def next_rev(self):\n"
        "        return self.tso.deal()\n"
    )
    assert leak_ids({PKG: src}) == []


def test_kb123_return_alias_transfer():
    src = (
        "class Backend:\n"
        "    def next_rev(self):\n"
        "        rev = self.tso.deal()\n"
        "        self._stamp(1)\n"
        "        return rev\n"
    )
    # the exc edge of _stamp still escapes with the obligation live
    assert leak_ids({PKG: src}) == ["KB123"]
    src_clean = (
        "class Backend:\n"
        "    def next_rev(self):\n"
        "        rev = self.tso.deal()\n"
        "        return rev\n"
    )
    assert leak_ids({PKG: src_clean}) == []


def test_kb123_resolved_callee_reaching_notify_transfers():
    """Passing the revision into a project callee that (transitively)
    feeds the sequencer transfers the obligation — the callee owns
    delivery now."""
    src = (
        "class Backend:\n"
        "    def commit(self, batch):\n"
        "        rev = self.tso.deal()\n"
        "        self._publish(rev)\n"
        "    def _publish(self, rev):\n"
        "        self._notify(rev)\n"
        "    def _notify(self, rev):\n"
        "        pass\n"
    )
    res = deep({PKG: src})
    assert [f.rule_id for f in res.findings
            if f.rule_id in LEAK_RULES] == []
    assert res.stats.get("leak_resolved_transfers", 0) >= 1


def test_kb123_unresolved_transfer_is_optimistic_and_counted():
    """A call the resolver cannot see takes the dealt revision: KB112-style
    honest blindness — optimistic transfer, counted, no finding."""
    src = (
        "class Backend:\n"
        "    def commit(self):\n"
        "        rev = self.tso.deal()\n"
        "        ship(rev)\n"             # ship: unknown to the graph
    )
    res = deep({PKG: src})
    assert [f.rule_id for f in res.findings
            if f.rule_id in LEAK_RULES] == []
    assert res.stats.get("leak_unresolved_transfers", 0) >= 1


def test_kb123_alias_closure_through_container():
    """The write-batch shape: the revision rides inside event records in a
    list; notifying the LIST discharges (container absorption + for-target
    back-link)."""
    src = (
        "class Backend:\n"
        "    def commit(self, ops):\n"
        "        rev = self.tso.deal()\n"
        "        events = []\n"
        "        for op in ops:\n"
        "            p = {}\n"
        "            p['rev'] = rev\n"
        "            events.append(p)\n"
        "        self._notify_many(events)\n"
    )
    # normal path discharges through the alias closure; the loop's iter /
    # dict construction cannot raise under the call-only exception model,
    # so no exception edge precedes the notify either
    assert leak_ids({PKG: src}) == []


# ------------------------------------------------------------------- KB124
# manual lock acquire / slot protocol not released on an exception edge.

KB124_LEAKY = (
    "import threading\n"
    "class S:\n"
    "    def __init__(self):\n"
    "        self._mu = threading.Lock()\n"
    "    def work(self):\n"
    "        self._mu.acquire()\n"
    "        self._step()\n"              # may raise -> lock held forever
    "        self._mu.release()\n"
    "    def _step(self):\n"
    "        pass\n"
)

KB124_CLEAN = (
    "import threading\n"
    "class S:\n"
    "    def __init__(self):\n"
    "        self._mu = threading.Lock()\n"
    "    def work(self):\n"
    "        self._mu.acquire()\n"
    "        try:\n"
    "            self._step()\n"
    "        finally:\n"
    "            self._mu.release()\n"
    "    def _step(self):\n"
    "        pass\n"
)


def test_kb124_acceptance_pair_manual_lock():
    """THE KB124 acceptance pair: .acquire() outside `with`, a raising
    call, release only on the normal path. The lockish-ness comes from the
    ctor prescan (attr named `_mu`, not `*lock`)."""
    res = deep({PKG: KB124_LEAKY})
    assert [f.rule_id for f in res.findings] == ["KB124"]
    (f,) = res.findings
    assert f.line == 6
    assert "self._mu.acquire()" in f.message
    assert "exception edge" in f.message
    assert leak_ids({PKG: KB124_CLEAN}) == []


def test_kb124_release_receiver_must_match():
    """Releasing a DIFFERENT lock in the finally does not discharge —
    receiver identity matters (`self._aux.release()` is not `_mu`)."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._mu = threading.Lock()\n"
        "        self._aux = threading.Lock()\n"
        "    def work(self):\n"
        "        self._mu.acquire()\n"
        "        try:\n"
        "            self._step()\n"
        "        finally:\n"
        "            self._aux.release()\n"
        "    def _step(self):\n"
        "        pass\n"
    )
    assert leak_ids({PKG: src}) == ["KB124"]


def test_kb124_guard_idiom_obligation_starts_at_fallthrough():
    """`if not lk.acquire(blocking=False): return` — the obligation only
    exists on the acquired arm; with try/finally there it is clean,
    without it the exception edge leaks."""
    clean = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._mu = threading.Lock()\n"
        "    def try_work(self):\n"
        "        if not self._mu.acquire(blocking=False):\n"
        "            return False\n"
        "        try:\n"
        "            self._step()\n"
        "        finally:\n"
        "            self._mu.release()\n"
        "        return True\n"
        "    def _step(self):\n"
        "        pass\n"
    )
    assert leak_ids({PKG: clean}) == []
    leaky = clean.replace(
        "        try:\n"
        "            self._step()\n"
        "        finally:\n"
        "            self._mu.release()\n",
        "        self._step()\n"
        "        self._mu.release()\n")
    assert leak_ids({PKG: leaky}) == ["KB124"]


def test_kb124_compound_condition_skipped_and_counted():
    """An acquire buried in a compound condition is too gnarly to place —
    skipped, never guessed, and the skip is counted."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._mu = threading.Lock()\n"
        "    def maybe(self, ok):\n"
        "        if ok and self._mu.acquire(blocking=False):\n"
        "            self._mu.release()\n"
    )
    res = deep({PKG: src})
    assert [f.rule_id for f in res.findings
            if f.rule_id in LEAK_RULES] == []
    assert res.stats.get("leak_skipped_conditional", 0) >= 1


def test_kb124_semaphore_kick_is_not_a_lock():
    """The wakeup-kick idiom: consuming a Semaphore token with
    acquire(blocking=False) is signal consumption, not lock acquisition —
    releasing it on exit would be the bug. No obligation."""
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._kick = threading.Semaphore(0)\n"
        "    def drain(self):\n"
        "        self._kick.acquire(blocking=False)\n"
        "        self._step()\n"
        "    def _step(self):\n"
        "        pass\n"
    )
    res = deep({PKG: src})
    assert [f.rule_id for f in res.findings
            if f.rule_id in LEAK_RULES] == []
    assert res.stats.get("kb124_sites", 0) == 0


def test_kb124_slot_protocol_and_queue_handoff():
    """The scheduler dispatcher protocol: _acquire_slot/_release_slot is a
    lock-like pair; queueing the request into a self-container hands the
    slot to the worker (sanctioned normal-path non-release), but an
    exception BEFORE the handoff leaks the slot."""
    leaky = (
        "class Sched:\n"
        "    def dispatch(self):\n"
        "        if self._acquire_slot():\n"
        "            req = self._take()\n"     # may raise -> slot leaked
        "            self._runq.append(req)\n"
        "    def _acquire_slot(self):\n"
        "        return True\n"
        "    def _take(self):\n"
        "        pass\n"
    )
    res = deep({PKG: leaky})
    assert [f.rule_id for f in res.findings] == ["KB124"]
    assert "_acquire_slot" in res.findings[0].message
    clean = (
        "class Sched:\n"
        "    def dispatch(self):\n"
        "        if self._acquire_slot():\n"
        "            try:\n"
        "                req = self._take()\n"
        "            except Exception:\n"
        "                self._release_slot()\n"
        "                raise\n"
        "            self._runq.append(req)\n"
        "    def _acquire_slot(self):\n"
        "        return True\n"
        "    def _release_slot(self):\n"
        "        pass\n"
        "    def _take(self):\n"
        "        pass\n"
    )
    assert leak_ids({PKG: clean}) == []


# ------------------------------------------------------------------- KB125
# registration leak: watcher / gauge / span / fault-plane registrations an
# exception edge can escape without the matching deregistration.

def test_kb125_watcher_acceptance_pair():
    leaky = (
        "class Front:\n"
        "    def watch(self, hub, key):\n"
        "        wid = hub.add_watcher(key)\n"
        "        self._prime(key)\n"          # may raise -> wid leaked
        "        self._wids[key] = wid\n"
        "    def _prime(self, key):\n"
        "        pass\n"
    )
    res = deep({PKG: leaky})
    assert [f.rule_id for f in res.findings] == ["KB125"]
    (f,) = res.findings
    assert f.line == 3
    assert "add_watcher" in f.message and "delete_watcher" in f.message
    clean = (
        "class Front:\n"
        "    def watch(self, hub, key):\n"
        "        wid = hub.add_watcher(key)\n"
        "        try:\n"
        "            self._prime(key)\n"
        "        except Exception:\n"
        "            hub.delete_watcher(wid)\n"
        "            raise\n"
        "        self._wids[key] = wid\n"
        "    def _prime(self, key):\n"
        "        pass\n"
    )
    assert leak_ids({PKG: clean}) == []


def test_kb125_watcher_handle_handed_to_component_transfers():
    """The wid handed to another component (reply message, registry) is an
    ownership transfer — that component owns the delete now."""
    src = (
        "class Front:\n"
        "    def watch(self, hub, key):\n"
        "        wid = hub.add_watcher(key)\n"
        "        self._reply(wid)\n"
        "    def _reply(self, wid):\n"
        "        pass\n"
    )
    assert leak_ids({PKG: src}) == []


def test_kb125_gauge_class_lifecycle_transfer():
    """Handle-less registrations (gauges) can only be cleaned up by the
    instance's own teardown: a matching unregister ANYWHERE in the class
    transfers the obligation to the instance lifecycle; a class that
    registers but never deregisters leaks — its instances can never be
    cleanly dropped."""
    leaky = (
        "class Exporter:\n"
        "    def start(self, metrics):\n"
        "        metrics.register_gauge_fn('kb_depth', self._depth)\n"
        "        self._boot()\n"              # may raise -> gauge leaked
        "    def _boot(self):\n"
        "        pass\n"
        "    def _depth(self):\n"
        "        return 0\n"
    )
    res = deep({PKG: leaky})
    assert [f.rule_id for f in res.findings] == ["KB125"]
    assert "register_gauge_fn" in res.findings[0].message
    clean = leaky + (
        "    def close(self, metrics):\n"
        "        metrics.unregister_gauge_fn('kb_depth')\n"
    )
    res2 = deep({PKG: clean})
    assert [f.rule_id for f in res2.findings
            if f.rule_id in LEAK_RULES] == []
    assert res2.stats.get("kb125_class_transfers", 0) >= 1


def test_kb125_hand_rolled_span_pair():
    """A directly-constructed Span must reach tracer.finish on the
    exception edge too; the Tracer.span CM (a `with` context) is the
    sanctioned shape and discharges by construction."""
    leaky = (
        "from kubebrain_tpu.trace import Span\n"
        "class H:\n"
        "    def handle(self, req):\n"
        "        sp = Span('range')\n"
        "        self._serve(req)\n"          # may raise -> never finished
        "        self.tracer.finish(sp)\n"
        "    def _serve(self, req):\n"
        "        pass\n"
    )
    res = deep({PKG: leaky})
    assert [f.rule_id for f in res.findings] == ["KB125"]
    assert "span sp" in res.findings[0].message
    clean = (
        "from kubebrain_tpu.trace import Span\n"
        "class H:\n"
        "    def handle(self, req):\n"
        "        sp = Span('range')\n"
        "        try:\n"
        "            self._serve(req)\n"
        "        finally:\n"
        "            self.tracer.finish(sp)\n"
        "    def _serve(self, req):\n"
        "        pass\n"
    )
    assert leak_ids({PKG: clean}) == []


def test_kb125_fault_plane_arm_requires_plane_receiver():
    """The arm/disarm pair only matches plane-ish receivers — `alarm.arm()`
    on some other object must not be claimed by the fault-plane rule."""
    leaky = (
        "class Chaos:\n"
        "    def boot(self, sched):\n"
        "        self._plane.arm(sched)\n"
        "        self._probe()\n"             # may raise -> armed forever
        "    def _probe(self):\n"
        "        pass\n"
    )
    res = deep({PKG: leaky})
    assert [f.rule_id for f in res.findings] == ["KB125"]
    not_a_plane = leaky.replace("self._plane.arm", "self._timer.arm")
    assert leak_ids({PKG: not_a_plane}) == []


# ------------------------------------------------------------------- KB126
# stream/channel/handle lifecycle: closed on all paths or transferred.

def test_kb126_acceptance_pair_grpc_channel():
    leaky = (
        "import grpc\n"
        "def probe(target):\n"
        "    ch = grpc.insecure_channel(target)\n"
        "    ch.ping()\n"                     # leaks on exc AND fall-through
    )
    res = deep({PKG: leaky})
    assert [f.rule_id for f in res.findings] == ["KB126"]
    (f,) = res.findings
    assert "grpc.insecure_channel() handle ch" in f.message
    assert "close" in f.message
    clean = (
        "import grpc\n"
        "def probe(target):\n"
        "    ch = grpc.insecure_channel(target)\n"
        "    try:\n"
        "        ch.ping()\n"
        "    finally:\n"
        "        ch.close()\n"
    )
    assert leak_ids({PKG: clean}) == []


def test_kb126_ownership_transfers():
    """The three transfer shapes: return the handle, store it on self,
    pass it to a consumer (Popen(stderr=fh) — the runner's server-log
    shape: the spawned process owns the close)."""
    src = (
        "import grpc\n"
        "import subprocess\n"
        "def dial(target):\n"
        "    ch = grpc.insecure_channel(target)\n"
        "    return ch\n"
        "class C:\n"
        "    def connect(self, target):\n"
        "        ch = grpc.insecure_channel(target)\n"
        "        self._ch = ch\n"
        "    def spawn(self, args, log_path):\n"
        "        fh = open(log_path, 'ab')\n"
        "        return subprocess.Popen(args, stderr=fh)\n"
    )
    assert leak_ids({PKG: src}) == []


def test_kb126_direct_self_store_is_not_trackable():
    """`self._ch = grpc.insecure_channel(t)` transfers to the instance at
    the acquire itself — no name binding, no obligation."""
    src = (
        "import grpc\n"
        "class C:\n"
        "    def connect(self, target):\n"
        "        self._ch = grpc.insecure_channel(target)\n"
        "        self._handshake()\n"
        "    def _handshake(self):\n"
        "        pass\n"
    )
    assert leak_ids({PKG: src}) == []


def test_kb126_guard_correlated_release():
    """`if fh: fh.close()` — the test re-checks the handle, so both arms
    are accounted for (path-insensitivity must not walk the skip arm with
    the obligation live)."""
    src = (
        "def read_opt(p):\n"
        "    fh = open(p)\n"
        "    if fh:\n"
        "        fh.close()\n"
    )
    assert leak_ids({PKG: src}) == []


def test_kb126_with_statement_discharges_by_construction():
    src = (
        "def read(p):\n"
        "    with open(p) as fh:\n"
        "        return fh.read()\n"
    )
    assert leak_ids({PKG: src}) == []


# ------------------------------------------------------ machinery contracts

def test_leak_rules_only_scope_kubebrain_package():
    """tools/ feeds the call graph but leak findings are scoped
    to the serving tree, like the other deep rules."""
    assert leak_ids({"tools/helper.py": KB123_LEAKY}) == []


def test_leak_pragma_suppression():
    src = KB123_LEAKY.replace(
        "        rev = self.tso.deal()\n",
        "        rev = self.tso.deal()  # kblint: disable=KB123\n")
    assert leak_ids({PKG: src}) == []


def test_leak_stats_and_static_report():
    """The obligations feed both the stats counters and the per-kind
    static leak report the cross-check consumes."""
    res = deep({PKG: KB123_LEAKY})
    assert res.stats.get("leak_acquires", 0) == 1
    assert res.stats.get("kb123_sites", 0) == 1
    assert res.leaks["site_count"] == 1
    assert res.leaks["by_kind"]["revision"] == {"sites": 1, "leaking": 1}
    sites = res.leaks["sites"]
    assert sites[0]["rule"] == "KB123" and sites[0]["leaks"] is True


def test_sources_none_skips_cfg_tier():
    """Summary-only replay (no ASTs) must skip KB123–KB126, not crash."""
    from tools.kblint.contexts import analyze
    from tools.kblint.graph import ProjectGraph, extract_module
    graph = ProjectGraph([extract_module(KB123_LEAKY, PKG)])
    res = analyze(graph, sources=None)
    assert [f.rule_id for f in res.findings
            if f.rule_id in LEAK_RULES] == []
    assert res.leaks == {}


def test_real_tree_has_no_leak_findings():
    """The regression anchor: the shipped serving tree is leak-clean (the
    leaks this PR fixed stay fixed) while the tier provably has work to do
    (obligations exist and span multiple kinds)."""
    res = deep_analyze_paths(REPO)
    leak_findings = [f for f in res.findings if f.rule_id in LEAK_RULES]
    assert leak_findings == [], [f.message for f in leak_findings]
    assert res.stats.get("leak_acquires", 0) >= 5
    assert {"revision", "handle"} <= set(res.leaks["by_kind"])


# ------------------------------------------------- runtime leak sanitizer

def _fresh_leakcheck():
    from kubebrain_tpu.util import leakcheck
    was = leakcheck.installed()
    if not was:
        leakcheck.install()
    leakcheck.take_violations()
    leakcheck.reset()
    return leakcheck, was


def test_leakcheck_span_leak_detected_at_teardown():
    """The KB125 runtime twin: a hand-rolled span never finished is swept
    (and reported) by the end-of-test teardown check."""
    from kubebrain_tpu import trace
    leakcheck, was = _fresh_leakcheck()
    try:
        sp = trace.Span("leaky-op")
        assert sp is not None
        found = leakcheck.check_teardown()
        assert len(found) == 1
        assert found[0].kind == "leaked-span"
        assert "leaky-op" in found[0].detail
        # the strict-guard drain sees the same violation exactly once
        drained = leakcheck.take_violations()
        assert [v.kind for v in drained] == ["leaked-span"]
        assert leakcheck.take_violations() == []
    finally:
        leakcheck.reset()
        if not was:
            leakcheck.uninstall()


def test_leakcheck_span_balanced_and_observed_schema():
    from kubebrain_tpu import trace
    leakcheck, was = _fresh_leakcheck()
    try:
        tracer = trace.Tracer()
        sp = trace.Span("ok-op")
        tracer.finish(sp)
        assert leakcheck.check_teardown() == []
        obs = leakcheck.observed()
        rec = next(o for o in obs if o["kind"] == "span")
        assert rec["acquired"] >= 1
        assert rec["released"] >= 1
        assert rec["outstanding"] == 0
        assert rec["violations"] == 0
    finally:
        leakcheck.reset()
        if not was:
            leakcheck.uninstall()


def test_leakcheck_live_export_cross_check_round_trip(tmp_path):
    """End-to-end: exercise the runtime sanitizer, export the observed
    balances, and feed them to the static cross-check of a fixture whose
    only obligation kind matches — the KB115 lock-graph / fieldcheck
    analog for leaks."""
    from kubebrain_tpu import trace
    leakcheck, was = _fresh_leakcheck()
    try:
        tracer = trace.Tracer()
        sp = trace.Span("rt-op")
        tracer.finish(sp)
        out = tmp_path / "leaks.json"
        n = leakcheck.export_observed(str(out))
        assert n >= 1
    finally:
        leakcheck.reset()
        if not was:
            leakcheck.uninstall()
    payload = json.loads(out.read_text())
    assert payload["format"] == "kblint-leak-observed/v1"
    obs = payload["kinds"]
    clean_span_src = (
        "from kubebrain_tpu.trace import Span\n"
        "class H:\n"
        "    def handle(self, req):\n"
        "        sp = Span('range')\n"
        "        try:\n"
        "            self._serve(req)\n"
        "        finally:\n"
        "            self.tracer.finish(sp)\n"
        "    def _serve(self, req):\n"
        "        pass\n"
    )
    res = deep({PKG: clean_span_src}, runtime_leak_obs=obs)
    rep = res.leaks
    assert "span" in rep["observed_kinds"]
    assert rep["observed_kinds"]["span"]["outstanding"] == 0
    assert rep["unbalanced_kinds"] == []
    assert rep["coverage"] == pytest.approx(1.0)  # static {span} observed
    assert rep["static_only_kinds"] == []


def test_leak_report_without_runtime_obs_is_static_only():
    res = deep({PKG: KB123_CLEAN})
    assert "observed_kinds" not in res.leaks
    assert res.leaks["by_kind"]["revision"]["leaking"] == 0


def test_cli_leak_flags_require_deep():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.kblint", "--leak-report",
         "kubebrain_tpu/backend"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode != 0
    assert "--deep" in (proc.stderr + proc.stdout)


# ------------------------------------------- fixed-leak regression shapes
# The product shapes this PR's triage fixed or proved clean, frozen as
# fixtures so a refactor that re-introduces the leak pattern fails here
# even before the real-tree run does.

def test_regression_backend_notify_in_finally_shape():
    """Backend.commit: deal -> mutate (can raise via injected faults) ->
    notify must sit in a finally, or chaos wedges the revision stream."""
    assert leak_ids({PKG: KB123_CLEAN}) == []
    assert leak_ids({PKG: KB123_LEAKY}) == ["KB123"]


def test_regression_scheduler_dispatch_handoff_shape():
    """RequestScheduler._dispatch: slot handed to the worker by queueing;
    release on the exception path only (the normal-path non-release IS the
    protocol)."""
    src = (
        "class Sched:\n"
        "    def _dispatch(self, req):\n"
        "        if not self._acquire_slot():\n"
        "            return False\n"
        "        try:\n"
        "            self._runq.append(req)\n"
        "        except Exception:\n"
        "            self._release_slot()\n"
        "            raise\n"
        "        return True\n"
        "    def _acquire_slot(self):\n"
        "        return True\n"
        "    def _release_slot(self):\n"
        "        pass\n"
    )
    assert leak_ids({PKG: src}) == []
