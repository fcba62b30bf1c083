"""The project-invariant rules. Importing this module populates RULES."""

from __future__ import annotations

import ast
import re
from typing import Iterable, Iterator

from .core import Rule, register


def dotted_name(node: ast.expr) -> str:
    """``a.b.c`` for Name/Attribute chains, "" for anything else."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def terminal_name(node: ast.expr) -> str:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def walk_same_scope(body: list[ast.stmt]) -> Iterator[ast.AST]:
    """Walk statements without descending into nested function/class defs —
    code inside a nested def runs later, under different conditions (e.g.
    an executor thunk defined in a coroutine, or a callback defined under a
    lock)."""
    stack: list[ast.AST] = list(body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef, ast.Lambda)):
            yield node  # the def statement itself, but not its contents
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


# Module-level callables that block the calling thread. Method calls on
# arbitrary objects (sock.recv, proc.wait) are untypeable statically and are
# the runtime lock-order detector's job (util/lockcheck.py).
_BLOCKING_CALLS = {
    "time.sleep",
    "socket.create_connection",
    "urllib.request.urlopen",
    "open",
}
_BLOCKING_MODULES = ("subprocess", "requests")


def _is_blocking_call(call: ast.Call) -> str | None:
    name = dotted_name(call.func)
    if name in _BLOCKING_CALLS:
        return name
    root = name.split(".", 1)[0]
    if root in _BLOCKING_MODULES:
        return name
    return None


@register
class NoBlockingInAsync(Rule):
    """An event-loop thread serves every watch stream on the port; one
    blocking call stalls them all. Blocking work belongs in
    ``run_in_executor``."""

    rule_id = "KB101"
    summary = "no blocking calls inside async def bodies (endpoint/, server/)"

    def applies(self, relpath: str) -> bool:
        return relpath.replace("\\", "/").startswith(
            ("kubebrain_tpu/endpoint/", "kubebrain_tpu/server/")
        )

    def check(self, tree: ast.Module, src: str) -> Iterable[tuple[ast.AST, str]]:
        for node in ast.walk(tree):
            if not isinstance(node, ast.AsyncFunctionDef):
                continue
            for inner in walk_same_scope(node.body):
                # nested async defs are visited by the outer ast.walk
                if isinstance(inner, ast.AsyncFunctionDef):
                    continue
                if isinstance(inner, ast.Call):
                    name = _is_blocking_call(inner)
                    if name:
                        yield inner, (
                            f"blocking call {name}() inside async def "
                            f"{node.name!r}; use run_in_executor"
                        )


_LOCK_NAME_RE = re.compile(r"lock$", re.IGNORECASE)


def _lock_expr(item: ast.withitem) -> str | None:
    name = terminal_name(item.context_expr)
    if name and _LOCK_NAME_RE.search(name):
        return dotted_name(item.context_expr) or name
    return None


@register
class NoDispatchUnderLock(Rule):
    """JAX dispatch can block on device availability and RPC/sleep on the
    network; either inside a ``threading.Lock`` region turns one slow call
    into a process-wide convoy (and, cross-lock, a deadlock)."""

    rule_id = "KB102"
    summary = "no JAX dispatch, RPC, or sleeps while holding a threading lock"

    def applies(self, relpath: str) -> bool:
        return relpath.replace("\\", "/").startswith("kubebrain_tpu/")

    def check(self, tree: ast.Module, src: str) -> Iterable[tuple[ast.AST, str]]:
        for node in ast.walk(tree):
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            locks = [l for l in (_lock_expr(i) for i in node.items) if l]
            if not locks:
                continue
            held = locks[0]
            for inner in walk_same_scope(node.body):
                if not isinstance(inner, ast.Call):
                    continue
                name = dotted_name(inner.func)
                if name.startswith("jax."):
                    yield inner, f"JAX dispatch {name}() while holding {held}"
                elif terminal_name(inner.func) == "block_until_ready":
                    yield inner, f"block_until_ready() while holding {held}"
                elif _is_blocking_call(inner):
                    yield inner, f"blocking call {name}() while holding {held}"


@register
class NoBareExcept(Rule):
    """A bare ``except:`` swallows KeyboardInterrupt/SystemExit and hides
    sequencer thread death as silent data loss."""

    rule_id = "KB103"
    summary = "no bare except clauses"

    def check(self, tree: ast.Module, src: str) -> Iterable[tuple[ast.AST, str]]:
        for node in ast.walk(tree):
            if isinstance(node, ast.ExceptHandler) and node.type is None:
                yield node, "bare except: name the exceptions (or use Exception)"


_JIT_NAMES = {"jax.jit", "jit", "pjit", "jax.pjit"}


def _is_jit_decorator(dec: ast.expr) -> bool:
    if dotted_name(dec) in _JIT_NAMES:
        return True
    if isinstance(dec, ast.Call):
        fname = dotted_name(dec.func)
        if fname in _JIT_NAMES:
            return True  # @jax.jit(static_argnums=...)
        if fname in ("partial", "functools.partial") and dec.args:
            return dotted_name(dec.args[0]) in _JIT_NAMES
    return False


@register
class NoHostSyncInJit(Rule):
    """``device_get``/``block_until_ready`` inside a jitted kernel breaks
    tracing purity: it either fails under jit or silently forces a host
    sync per dispatch, destroying the scan kernel's pipelining."""

    rule_id = "KB104"
    summary = "no jax.device_get / block_until_ready inside @jax.jit kernels (ops/)"

    def applies(self, relpath: str) -> bool:
        return relpath.replace("\\", "/").startswith("kubebrain_tpu/ops/")

    def check(self, tree: ast.Module, src: str) -> Iterable[tuple[ast.AST, str]]:
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not any(_is_jit_decorator(d) for d in node.decorator_list):
                continue
            for inner in walk_same_scope(node.body):
                if not isinstance(inner, ast.Call):
                    continue
                name = dotted_name(inner.func)
                if name in ("jax.device_get", "device_get"):
                    yield inner, f"host sync {name}() inside jitted {node.name!r}"
                elif terminal_name(inner.func) == "block_until_ready":
                    yield inner, f"block_until_ready() inside jitted {node.name!r}"


_TIME_TIME_MODULES = re.compile(r"^_?time$")


def _is_time_time(call: ast.Call) -> bool:
    """``time.time()`` (including aliased imports like ``_time.time()``)."""
    func = call.func
    return (
        isinstance(func, ast.Attribute)
        and func.attr == "time"
        and isinstance(func.value, ast.Name)
        and bool(_TIME_TIME_MODULES.match(func.value.id))
    )


def _contains_time_time(expr: ast.expr) -> bool:
    return any(
        isinstance(n, ast.Call) and _is_time_time(n) for n in ast.walk(expr)
    )


@register
class NoPrintOrRawLatency(Rule):
    """Serving-path observability goes through the tracer/metrics facade:
    ``print()`` writes to a stdout nobody scrapes (and blocks on a full
    pipe), and hand-rolled ``time.time() - t0`` latency math measures wall
    clock (jumps on NTP steps) and is invisible to /metrics and
    /debug/traces. Use ``trace.TRACER.stage(...)``/``record_stage`` or
    ``metrics.timed(...)``/``emit_histogram``."""

    rule_id = "KB107"
    summary = ("no print() and no raw time.time() latency measurement in "
               "server/, sched/, endpoint/ — use trace/metrics helpers")

    def applies(self, relpath: str) -> bool:
        return relpath.replace("\\", "/").startswith(
            ("kubebrain_tpu/server/", "kubebrain_tpu/sched/",
             "kubebrain_tpu/endpoint/")
        )

    def check(self, tree: ast.Module, src: str) -> Iterable[tuple[ast.AST, str]]:
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                    and node.func.id == "print":
                yield node, ("print() on the serving path; use logging or "
                             "the metrics/trace facade")
            elif isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub):
                if _contains_time_time(node.left) or _contains_time_time(node.right):
                    yield node, (
                        "raw time.time() latency measurement; use "
                        "trace.TRACER.stage()/metrics.timed() (monotonic, "
                        "lands on /metrics and /debug/traces)"
                    )


_TTL_TOKENS = {"ttl", "deadline", "deadlines", "expire", "expires", "expired",
               "expiry", "lease", "leases", "keepalive"}


def _ttlish(expr: ast.expr) -> str | None:
    """The dotted name of the first TTL/deadline-carrying Name/Attribute
    inside ``expr`` ('ttl', 'deadline', 'lease.expires_at', ...)."""
    for node in ast.walk(expr):
        name = terminal_name(node) if isinstance(node, (ast.Name, ast.Attribute)) else ""
        if name and _TTL_TOKENS & set(name.lower().split("_")):
            return dotted_name(node) or name
    return None


@register
class MonotonicLeaseClock(Rule):
    """Wall-clock TTL math breaks under clock steps: an NTP jump (or VM
    suspend/resume) either mass-expires every lease or grants them hours of
    free life. Live deadlines belong on the monotonic clock —
    ``kubebrain_tpu/lease/clock.py`` is the one serving-path module allowed
    to touch the conversion."""

    rule_id = "KB108"
    summary = ("no time.time() TTL/deadline arithmetic on the serving path "
               "outside kubebrain_tpu/lease/clock.py — use lease.clock")

    def applies(self, relpath: str) -> bool:
        rp = relpath.replace("\\", "/")
        if rp == "kubebrain_tpu/lease/clock.py":
            return False
        return rp.startswith((
            "kubebrain_tpu/lease/", "kubebrain_tpu/backend/",
            "kubebrain_tpu/server/", "kubebrain_tpu/sched/",
            "kubebrain_tpu/endpoint/",
        ))

    def check(self, tree: ast.Module, src: str) -> Iterable[tuple[ast.AST, str]]:
        for node in ast.walk(tree):
            if isinstance(node, ast.BinOp) and isinstance(node.op, (ast.Add, ast.Sub)):
                sides = (node.left, node.right)
                if any(_contains_time_time(s) for s in sides):
                    name = _ttlish(node.left) or _ttlish(node.right)
                    if name:
                        yield node, (
                            f"wall-clock TTL/deadline arithmetic with {name!r}; "
                            "use kubebrain_tpu.lease.clock (monotonic)"
                        )
            elif isinstance(node, ast.Compare):
                exprs = (node.left, *node.comparators)
                if any(_contains_time_time(e) for e in exprs):
                    name = next((t for e in exprs if (t := _ttlish(e))), None)
                    if name:
                        yield node, (
                            f"wall-clock deadline comparison with {name!r}; "
                            "use kubebrain_tpu.lease.clock (monotonic)"
                        )
            elif isinstance(node, ast.Assign):
                # deadline = time.time() + 30 — ttl-ish target, constant rhs
                value = node.value
                if not (isinstance(value, ast.BinOp)
                        and isinstance(value.op, (ast.Add, ast.Sub))
                        and _contains_time_time(value)):
                    continue
                if _ttlish(value.left) or _ttlish(value.right):
                    continue  # the BinOp branch reports this one
                for target in node.targets:
                    name = _ttlish(target) if isinstance(
                        target, (ast.Name, ast.Attribute)) else None
                    if name:
                        yield node, (
                            f"wall-clock deadline assigned to {name!r}; "
                            "use kubebrain_tpu.lease.clock (monotonic)"
                        )
                        break


#: the device scan-kernel entry points (ops.scan_pallas + the engine's jit
#: wrappers). Launching one anywhere except the engine's assembly points
#: forks the query-packing logic: a stray call site can silently disagree
#: with `_dev_mask`/`_dev_mask_batch` on bound canonicalization, revision
#: splitting, pow2 padding, or the kernel/mesh selection — exactly the
#: drift the single-assembly-point discipline exists to prevent.
_SCAN_DISPATCH_NAMES = {
    "scan_mask_pallas", "scan_mask_pallas_q",
    "visibility_mask_batch", "visibility_mask_batch_cached",
    "visibility_mask_batch_cached_q",
    "_vis_batch", "_vis_batch_q", "_vis_batch_pallas", "_vis_batch_pallas_q",
    "_vis_rows",
}
#: functions allowed to reference them: the two engine assembly points and
#: the module-level jit wrappers those assembly points dispatch through
_SCAN_DISPATCH_ALLOWED = {
    "_dev_mask", "_dev_mask_batch",
    "_vis_batch", "_vis_batch_q", "_vis_batch_pallas", "_vis_batch_pallas_q",
    "_vis_rows",
}


@register
class ScanDispatchOnlyInAssemblyPoints(Rule):
    """Device scan dispatch in the scheduler/TPU-engine layers may only
    happen inside the `_dev_mask`/`_dev_mask_batch` assembly points (and
    the engine's own jit wrappers they call) — stray
    `scan_mask_pallas`/`visibility_mask_batch` call sites bypass the one
    place query packing, Q padding, and kernel selection are kept
    coherent."""

    rule_id = "KB109"
    summary = ("device scan kernels may only be dispatched from the "
               "_dev_mask/_dev_mask_batch assembly points "
               "(sched/, storage/tpu/)")

    def applies(self, relpath: str) -> bool:
        return relpath.replace("\\", "/").startswith(
            ("kubebrain_tpu/sched/", "kubebrain_tpu/storage/tpu/")
        )

    def check(self, tree: ast.Module, src: str) -> Iterable[tuple[ast.AST, str]]:
        def scan(body: list[ast.stmt],
                 func_name: str | None) -> Iterator[tuple[ast.AST, str]]:
            allowed = func_name in _SCAN_DISPATCH_ALLOWED
            for node in walk_same_scope(body):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield from scan(node.body, node.name)
                    continue
                if isinstance(node, ast.ClassDef):
                    # methods are where the engine's dispatch code lives —
                    # walk_same_scope stops at the class header, so descend
                    # explicitly (class-level statements get no allowance)
                    yield from scan(node.body, None)
                    continue
                if isinstance(node, ast.Lambda):
                    # a lambda belongs to its enclosing def (the engine
                    # wrappers close over the kernel via lambdas)
                    yield from scan([ast.Expr(value=node.body)], func_name)
                    continue
                if allowed:
                    continue
                # both direct calls and bare references count — wrapping a
                # kernel in vmap/partial outside an assembly point is the
                # same bypass as calling it
                name = None
                if isinstance(node, (ast.Name, ast.Attribute)):
                    name = terminal_name(node)
                if name in _SCAN_DISPATCH_NAMES:
                    where = f" (in {func_name!r})" if func_name else ""
                    yield node, (
                        f"device scan dispatch {name}{where}: kernels may "
                        "only launch from the _dev_mask/_dev_mask_batch "
                        "assembly points"
                    )

        yield from scan(tree.body, None)


#: module-level PRNG roots whose use makes a workload non-replayable
#: (names are matched after alias canonicalization, so ``import random as
#: r`` / ``from random import random`` don't slip through)
_UNSEEDED_RNG_PREFIXES = ("random.", "numpy.random.")
#: constructors that ARE the sanctioned way in — but only with an explicit
#: seed argument (``random.Random()`` falls back to urandom/wall clock)
_SEEDED_RNG_CTORS = {
    "random.Random", "numpy.random.default_rng", "numpy.random.RandomState",
}
_RNG_MODULES = {"random", "numpy", "numpy.random"}


def _rng_alias_maps(tree: ast.Module) -> tuple[dict, dict]:
    """(root alias -> canonical module, from-imported name -> canonical
    dotted name) for the RNG modules — the same aliased-import diligence
    ``_is_time_time`` applies to ``time``."""
    roots: dict[str, str] = {}
    from_names: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name in _RNG_MODULES:
                    if a.asname:
                        roots[a.asname] = a.name
                    else:
                        # `import numpy.random` binds the TOP-LEVEL package
                        # name, so the canonical mapping is the identity —
                        # mapping root -> full dotted module would mangle
                        # numpy.array into numpy.random.array
                        root = a.name.split(".", 1)[0]
                        roots.setdefault(root, root)
        elif isinstance(node, ast.ImportFrom) and node.module in _RNG_MODULES:
            for a in node.names:
                from_names[a.asname or a.name] = f"{node.module}.{a.name}"
    return roots, from_names


def _canon_rng_name(name: str, roots: dict, from_names: dict) -> str:
    if name in from_names:
        return from_names[name]
    root, _, rest = name.partition(".")
    if root in roots:
        return roots[root] + ("." + rest if rest else "")
    return name


@register
class ReplayableWorkloadRandomness(Rule):
    """The workload generator's contract is seed ⇒ byte-identical op
    trace (the replay harness's identity, asserted by the determinism
    test AND re-checked on every run). One ``random.random()`` or
    ``time.time()`` on the schedule path silently breaks replays in a way
    no single run can detect — the trace still *looks* plausible. Thread
    the seeded ``random.Random(seed)`` through instead, and use the event
    wheel / monotonic clock for time."""

    rule_id = "KB110"
    summary = ("workload/ must stay replayable: no unseeded randomness "
               "(module-level random.*/np.random.*) and no time.time() — "
               "thread a seeded random.Random; clock via the event wheel")

    def applies(self, relpath: str) -> bool:
        # faults/ carries the same replayability contract: the fault
        # schedule's sha IS the chaos run's replay identity
        p = relpath.replace("\\", "/")
        return (p.startswith("kubebrain_tpu/workload/")
                or p.startswith("kubebrain_tpu/faults/"))

    def check(self, tree: ast.Module, src: str) -> Iterable[tuple[ast.AST, str]]:
        roots, from_names = _rng_alias_maps(tree)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = _canon_rng_name(dotted_name(node.func), roots, from_names)
            if name in _SEEDED_RNG_CTORS:
                if not node.args and not node.keywords:
                    yield node, (
                        f"{name}() without a seed falls back to wall-clock/"
                        "urandom entropy; pass the spec seed"
                    )
                continue
            if name.startswith(_UNSEEDED_RNG_PREFIXES):
                yield node, (
                    f"module-level PRNG call {name}(): unseeded global "
                    "state breaks seed->trace determinism; use the "
                    "threaded random.Random(seed)"
                )
            elif _is_time_time(node):
                yield node, (
                    "time.time() in workload/: wall-clock reads make the "
                    "schedule non-replayable; use the event wheel "
                    "(simulated time) or time.monotonic() for measurement"
                )


#: device-array producers on the TPU engine's scan/compact path: a host
#: conversion of anything these return (or of a ``*_dev`` mirror column) is
#: a device→host transfer, and outside the named materialization points it
#: is exactly the accidental full-mirror gather that killed the multichip
#: dry run on real traffic
_DEVICE_PRODUCER_NAMES = {
    "_vis_batch", "_vis_batch_q", "_vis_batch_pallas", "_vis_batch_pallas_q",
    "_vis_rows", "_part_indices_of_mask", "_part_indices_of_mask_sel",
    "_part_survivor_indices", "_survivor_mask", "_victim_part_counts",
    "_victim_batch", "_victim_batch_pallas", "_dev_mask", "_dev_mask_batch",
}
#: numpy host-conversion entry points (device arrays convert implicitly)
_HOST_CONVERTERS = {
    "np.asarray", "numpy.asarray", "np.array", "numpy.array",
    "np.copy", "numpy.copy",
}
#: the named materialization points allowed to pull device data to host in
#: storage/tpu/ — everything else must go through `_host_pull` (which both
#: blocks correctly and meters the bytes for the transfer-budget tests)
_HOST_TRANSFER_ALLOWED = {
    "_host_pull", "_materialize_visible", "_materialize_wire",
    "_host_visible", "_host_visible_batch", "_pallas_ttl8", "_pull_victim_indices",
    "merge_partitions_incremental",
    # the compaction pipeline's named funnels (docs/compaction.md): the
    # victim-only decode point and the stored-domain mirror-maintenance
    # paths that rebuild sharded device arrays from host columns
    "_compact_victim_rows", "compact_partitions_stored",
    "merge_partitions_stored",
}


def _deviceish_expr(expr: ast.expr) -> str | None:
    """The name making ``expr`` a device-array expression, if any: a
    ``*_dev`` mirror column reference or a call to a device producer."""
    for node in ast.walk(expr):
        if isinstance(node, (ast.Name, ast.Attribute)):
            t = terminal_name(node)
            if t.endswith("_dev"):
                return dotted_name(node) or t
        if isinstance(node, ast.Call):
            t = terminal_name(node.func)
            if t in _DEVICE_PRODUCER_NAMES:
                return t
    return None


@register
class HostTransferOnlyAtMaterializationPoints(Rule):
    """In ``storage/tpu/`` every device→host pull must happen at a named
    materialization point (`_host_pull` and friends): a stray
    ``np.asarray(mirror.keys_dev)`` or ``jax.device_get(mask)`` silently
    re-introduces the full-mirror gather the shard-local scan path exists
    to prevent — O(dataset) bytes over the device link per scan instead of
    O(visible rows) — and dodges the transfer meter the budget tests
    audit."""

    rule_id = "KB111"
    summary = ("storage/tpu/: jax.device_get / host conversion of device "
               "arrays only inside the named materialization points "
               "(_host_pull, _materialize_visible/_wire, _host_visible*, "
               "_pallas_ttl8, _pull_victim_indices)")

    def applies(self, relpath: str) -> bool:
        return relpath.replace("\\", "/").startswith("kubebrain_tpu/storage/tpu/")

    def check(self, tree: ast.Module, src: str) -> Iterable[tuple[ast.AST, str]]:
        def scan(body: list[ast.stmt],
                 func_name: str | None) -> Iterator[tuple[ast.AST, str]]:
            allowed = func_name in _HOST_TRANSFER_ALLOWED
            for node in walk_same_scope(body):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield from scan(node.body, node.name)
                    continue
                if isinstance(node, ast.ClassDef):
                    yield from scan(node.body, None)
                    continue
                if isinstance(node, ast.Lambda):
                    yield from scan([ast.Expr(value=node.body)], func_name)
                    continue
                if allowed or not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func)
                where = f" (in {func_name!r})" if func_name else ""
                if name in ("jax.device_get", "device_get"):
                    yield node, (
                        f"device→host transfer {name}(){where}: only the "
                        "named materialization points may pull device data "
                        "(use _host_pull)"
                    )
                elif name in _HOST_CONVERTERS:
                    dev = next(
                        (d for a in (*node.args, *(kw.value for kw in node.keywords))
                         if (d := _deviceish_expr(a))), None)
                    if dev:
                        yield node, (
                            f"implicit device→host transfer {name}({dev}...)"
                            f"{where}: only the named materialization points "
                            "may pull device data (use _host_pull)"
                        )

        yield from scan(tree.body, None)


#: the decode primitives that turn ENCODED mirror rows back into raw key
#: bytes (storage/tpu/encode.py), and the funnels allowed to call each
#: tier: primitives only inside the Mirror decode funnel, the funnel only
#: inside the named materialization/rebuild paths. Everything else must
#: receive decoded bytes FROM those paths — a stray decode call is an
#: unmetered host materialization of key bytes the compressed-mirror
#: design exists to avoid (it dodges both the visible-row sizing and the
#: transfer-budget accounting).
_DECODE_PRIMITIVES = {"decode_rows", "decode_one"}
_DECODE_PRIMITIVE_FUNNELS = {"decoded_keys", "user_key"}
#: NOTE: ``compact`` itself is deliberately NOT here — since the
#: stored-domain compaction (docs/compaction.md) the only decode the
#: compact pipeline may perform is the victim-only funnel
#: ``_compact_victim_rows``; a whole-partition ``decoded_keys`` call from
#: ``compact`` (the pre-PR-12 shape) is exactly the host decode tax the
#: pipeline removed, and must be flagged.
#: The wire read (``_materialize_wire``) is in neither set: since PR 34 it
#: calls no Python decode at all — ``kb_wire_read`` decodes each visible row
#: in C as it writes the reply (``wire_key``, native/kbstore.cc), a twin of
#: ``decoded_keys`` that tests/test_wire_read.py holds to it; a Python
#: decode that came back onto the wire path would be flagged here.
_DECODE_FUNNEL_CALLERS = {
    "materialize", "flat_arrays", "merge_partitions_incremental",
    "_compact_victim_rows", "_materialize_visible",
}


@register
class DecodeOnlyAtMaterializationFunnels(Rule):
    """Decoded key bytes may only leave the encoded mirror through the
    named funnels: ``KeyEncoding.decode_rows``/``decode_one`` inside
    ``Mirror.decoded_keys``/``user_key``, and ``decoded_keys`` itself only
    from the materialization/rebuild paths (``materialize``,
    ``flat_arrays``, ``merge_partitions_incremental``, and compaction's
    victim-only ``_compact_victim_rows``). A decode call anywhere else
    re-creates the full-width key column on the host outside the
    visible-row/victim-row sizing — the exact cost the prefix-compressed
    mirror (docs/compression.md) and the stored-domain compaction
    (docs/compaction.md) remove. In particular a whole-partition decode
    from ``compact`` itself — the pre-stored-domain shape — is flagged.
    The one decode this rule cannot see is the wire read's, in C inside
    ``kb_wire_read`` (sized by the visible rows like the funnels, and held
    equal to ``decoded_keys`` by tests/test_wire_read.py)."""

    rule_id = "KB116"
    summary = ("storage/tpu/: encoded-key decode only through the "
               "decoded_keys/user_key funnels, themselves only from the "
               "named materialization/rebuild paths")

    def applies(self, relpath: str) -> bool:
        p = relpath.replace("\\", "/")
        # encode.py IS the implementation being confined; its internal
        # delegation (decode_one → decode_rows) is the primitive itself
        return (p.startswith("kubebrain_tpu/storage/tpu/")
                and not p.endswith("/encode.py"))

    def check(self, tree: ast.Module, src: str) -> Iterable[tuple[ast.AST, str]]:
        def scan(body: list[ast.stmt],
                 func_name: str | None) -> Iterator[tuple[ast.AST, str]]:
            for node in walk_same_scope(body):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield from scan(node.body, node.name)
                    continue
                if isinstance(node, ast.ClassDef):
                    yield from scan(node.body, None)
                    continue
                if not isinstance(node, ast.Call):
                    continue
                name = terminal_name(node.func)
                where = f" (in {func_name!r})" if func_name else ""
                if (name in _DECODE_PRIMITIVES
                        and func_name not in _DECODE_PRIMITIVE_FUNNELS):
                    yield node, (
                        f"raw-key decode {name}(){where}: only the "
                        "Mirror.decoded_keys/user_key funnels may call the "
                        "decode primitives"
                    )
                elif (name == "decoded_keys"
                        and func_name not in _DECODE_FUNNEL_CALLERS
                        and func_name != "decoded_keys"):
                    yield node, (
                        f"decoded_keys(){where}: decoded key bytes only "
                        "leave the mirror through the named materialization"
                        "/rebuild paths (materialize, flat_arrays, "
                        "merge_partitions_incremental, _compact_victim_rows)"
                    )

        yield from scan(tree.body, None)


#: the ONE dispatch point where raw query bounds meet the mirror's compare
#: domain (raw packed chunks or dictionary-encoded rows), plus the host
#: probe path that routes per-key through the same encoding check — every
#: other function must pass bounds through them, never pack its own
_BOUND_DOMAIN_FUNNELS = {"_bound_rows", "_host_visible_batch"}
_RAW_BOUND_PACKERS = {"pack_one"}
_ENCODED_BOUND_HELPERS = {"encode_start_bound", "encode_end_bound",
                          "encode_probe"}


@register
class BoundDomainDispatchOnly(Rule):
    """Raw-domain bound packing (``keyops.pack_one``) and encoded-domain
    bound helpers (``encode_*_bound``/``encode_probe``) are only callable
    inside the engine's domain-dispatch funnels (``_bound_rows``,
    ``_host_visible_batch``) — the naming rule that makes it impossible to
    hand a raw-domain bound to an encoded-mirror compare (or vice versa):
    the only code that sees both domains is the dispatch that checks
    ``mirror.encoding`` first."""

    rule_id = "KB117"
    summary = ("storage/tpu/: bound packing/encoding only inside the "
               "domain-dispatch funnels (_bound_rows, _host_visible_batch) "
               "— kernels must never see a bound from the wrong key domain")

    def applies(self, relpath: str) -> bool:
        p = relpath.replace("\\", "/")
        return (p.startswith("kubebrain_tpu/storage/tpu/")
                and not p.endswith("/encode.py"))

    def check(self, tree: ast.Module, src: str) -> Iterable[tuple[ast.AST, str]]:
        def scan(body: list[ast.stmt],
                 func_name: str | None) -> Iterator[tuple[ast.AST, str]]:
            for node in walk_same_scope(body):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield from scan(node.body, node.name)
                    continue
                if isinstance(node, ast.ClassDef):
                    yield from scan(node.body, None)
                    continue
                if not isinstance(node, ast.Call):
                    continue
                if func_name in _BOUND_DOMAIN_FUNNELS:
                    continue
                name = terminal_name(node.func)
                where = f" (in {func_name!r})" if func_name else ""
                if name in _RAW_BOUND_PACKERS:
                    yield node, (
                        f"raw-domain bound packing {name}(){where}: pack "
                        "query bounds through _bound_rows so an encoded "
                        "mirror never compares a raw-domain bound"
                    )
                elif name in _ENCODED_BOUND_HELPERS:
                    yield node, (
                        f"encoded-domain bound helper {name}(){where}: "
                        "encode query bounds through _bound_rows/"
                        "_host_visible_batch so a raw mirror never "
                        "compares an encoded-domain bound"
                    )

        yield from scan(tree.body, None)


_REV_TOKENS = {"rev", "revision"}


def _revision_like(expr: ast.expr) -> str | None:
    """The dotted name of the first revision-carrying Name/Attribute inside
    ``expr``, if any ('rev', 'guard_rev', 'request.revision', ...)."""
    for node in ast.walk(expr):
        name = terminal_name(node) if isinstance(node, (ast.Name, ast.Attribute)) else ""
        if name and _REV_TOKENS & set(name.lower().split("_")):
            return dotted_name(node) or name
    return None


#: backend/scanner range-read entry points the service layer must reach
#: through the request scheduler (kubebrain_tpu/sched), never directly —
#: a direct call bypasses admission lanes, coalescing, and overload
#: shedding, so one unthrottled caller can starve the device pipeline.
_SCAN_ENTRY_POINTS = {
    "list_", "list_wire", "list_by_stream", "count", "range_", "range_stream",
    "list_batch", "scan_batch",
}
_SCAN_RECEIVERS = {"backend", "scanner"}
#: backend write entry points — same funnel discipline for the write path
#: (docs/writes.md): service code reaches create/update/delete through the
#: scheduler's write lanes so group commit + admission control apply.
_WRITE_ENTRY_POINTS = {"create", "update", "delete"}
#: ``write_batch`` is the engine/backend group-commit executor itself; the
#: ONLY caller is the scheduler's batch dispatch (sched/scheduler.py) and
#: the backend core — in the service layer it is flagged on ANY receiver,
#: so aliasing the backend (``b = self.backend; b.write_batch(...)``)
#: cannot launder a direct group commit past the admission queue.
_GROUP_COMMIT_ENTRY = "write_batch"


@register
class RangeReadsThroughScheduler(Rule):
    """Service-layer range reads AND writes go through the request
    scheduler (``sched.ensure_scheduler``/the KVService ``limiter``);
    calling the backend/scanner scan or write entry points directly skips
    priority lanes, group commit, and overload protection."""

    rule_id = "KB106"
    summary = ("service-layer code must not call engine scan/write entry "
               "points directly (server/etcd/, endpoint/); use the scheduler")

    def applies(self, relpath: str) -> bool:
        return relpath.replace("\\", "/").startswith(
            ("kubebrain_tpu/server/etcd/", "kubebrain_tpu/endpoint/")
        )

    def check(self, tree: ast.Module, src: str) -> Iterable[tuple[ast.AST, str]]:
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if not isinstance(func, ast.Attribute):
                continue
            receiver = terminal_name(func.value)
            if func.attr == _GROUP_COMMIT_ENTRY:
                yield node, (
                    f"direct group-commit call {receiver}.{func.attr}(); "
                    "write groups form ONLY in the scheduler's dispatch "
                    "(sched.ensure_scheduler create/update/delete)"
                )
                continue
            if func.attr in _SCAN_ENTRY_POINTS:
                if receiver in _SCAN_RECEIVERS:
                    yield node, (
                        f"direct scan call {receiver}.{func.attr}(); range "
                        "reads go through the request scheduler "
                        "(sched.ensure_scheduler)"
                    )
            elif func.attr in _WRITE_ENTRY_POINTS and receiver == "backend":
                yield node, (
                    f"direct write call {receiver}.{func.attr}(); writes go "
                    "through the scheduler's write lanes "
                    "(sched.ensure_scheduler) so group commit and admission "
                    "control apply"
                )


@register
class RevisionFlowsThroughHelpers(Rule):
    """Revisions are opaque monotonic tokens minted by the sequencer; raw
    arithmetic in the etcd surface invents revisions the backend never
    issued. Transformations live in server/service/revision.py helpers."""

    rule_id = "KB105"
    summary = "revision arithmetic in server/etcd/ must use revision.py helpers"

    def applies(self, relpath: str) -> bool:
        return relpath.replace("\\", "/").startswith("kubebrain_tpu/server/etcd/")

    def check(self, tree: ast.Module, src: str) -> Iterable[tuple[ast.AST, str]]:
        arith = (ast.Add, ast.Sub, ast.Mult, ast.FloorDiv, ast.Div, ast.Mod)

        def _is_text(n: ast.expr) -> bool:
            # serializing a revision into a bytes/str frame is encoding,
            # not revision arithmetic
            if isinstance(n, ast.Constant) and isinstance(n.value, (str, bytes)):
                return True
            return isinstance(n, ast.BinOp) and (_is_text(n.left) or _is_text(n.right))

        for node in ast.walk(tree):
            if isinstance(node, ast.BinOp) and isinstance(node.op, arith):
                if isinstance(node.op, ast.Add) and (_is_text(node.left) or _is_text(node.right)):
                    continue
                name = _revision_like(node.left) or _revision_like(node.right)
                if name:
                    yield node, (
                        f"raw arithmetic on revision value {name!r}; use a "
                        "server/service/revision.py helper"
                    )
            elif isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
                name = _revision_like(node.operand)
                if name:
                    yield node, (
                        f"raw negation of revision value {name!r}; use a "
                        "server/service/revision.py helper"
                    )
            elif isinstance(node, ast.AugAssign) and isinstance(node.op, arith):
                name = _revision_like(node.target)
                if name:
                    yield node, (
                        f"raw in-place arithmetic on revision value {name!r}; "
                        "use a server/service/revision.py helper"
                    )


# --------------------------------------------------------------------- KB118
#: names whose presence in a loop suggests the retry count/window is bounded
_RETRY_BOUND_RE = re.compile(
    r"attempt|retr|tries|deadline|budget|remain|give_up|max_|horizon",
    re.IGNORECASE)
#: names whose presence in a sleep argument suggests jittered backoff
_JITTER_RE = re.compile(r"jitter|random|uniform|backoff|expov|rng",
                        re.IGNORECASE)
_LOCKISH_RE = re.compile(r"lock|mutex|cond", re.IGNORECASE)


def _handler_swallows(handler: ast.ExceptHandler) -> bool:
    """True when the except body neither re-raises, exits the loop, nor
    captures the exception for delivery — the shape that turns a loop
    into a retry loop. A handler that binds ``as e`` and then USES ``e``
    is delivering the error somewhere (a waiter, a result slot), not
    retrying past it."""
    for node in ast.walk(handler):
        if isinstance(node, (ast.Raise, ast.Return, ast.Break)):
            return False
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return False  # nested defs run later; be conservative
    if handler.name:
        for node in ast.walk(handler):
            if isinstance(node, ast.Name) and node.id == handler.name \
                    and isinstance(node.ctx, ast.Load):
                return False
    return True


def _loop_names(loop: ast.AST) -> set[str]:
    out: set[str] = set()
    for node in walk_same_scope(getattr(loop, "body", [])):
        if isinstance(node, ast.Name):
            out.add(node.id)
        elif isinstance(node, ast.Attribute):
            out.add(node.attr)
    # the loop test itself may carry the bound (while attempts < N)
    test = getattr(loop, "test", None)
    if test is not None:
        for node in ast.walk(test):
            if isinstance(node, ast.Name):
                out.add(node.id)
            elif isinstance(node, ast.Attribute):
                out.add(node.attr)
    return out


def _is_while_true(loop: ast.AST) -> bool:
    return (isinstance(loop, ast.While)
            and isinstance(loop.test, ast.Constant)
            and bool(loop.test.value))


def _sleep_calls(body: list[ast.stmt]) -> Iterator[ast.Call]:
    for node in walk_same_scope(body):
        if isinstance(node, ast.Call) and dotted_name(node.func) in (
                "time.sleep", "sleep"):
            yield node


def _sleep_has_jitter(call: ast.Call) -> bool:
    for arg in list(call.args) + [kw.value for kw in call.keywords]:
        for node in ast.walk(arg):
            if isinstance(node, (ast.Name, ast.Attribute)):
                if _JITTER_RE.search(terminal_name(node) or ""):
                    return True
            if isinstance(node, ast.Call):
                if _JITTER_RE.search(terminal_name(node.func) or ""):
                    return True
    return False


def _locks_enclosing(tree: ast.Module, line: int) -> list[ast.AST]:
    """With-blocks whose context expression names a lock and whose span
    covers ``line`` (lexical only — the transitive case is KB112's)."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        end = getattr(node, "end_lineno", 0) or 0
        if not (node.lineno <= line <= end):
            continue
        for item in node.items:
            name = dotted_name(item.context_expr) or terminal_name(
                item.context_expr)
            if isinstance(item.context_expr, ast.Call):
                name = dotted_name(item.context_expr.func)
            if name and _LOCKISH_RE.search(name.rsplit(".", 1)[-1]):
                out.append(node)
    return out


@register
class RetryLoopHygiene(Rule):
    """Serving-path retry loops must be BOUNDED, BACKED OFF WITH JITTER,
    and never sleep while holding a lock (docs/faults.md). The chaos
    harness makes every engine call failable — an unbounded `while True`
    retry with a constant sleep turns one injected fault window into a
    convoy: every retrier wakes at the same instant forever, and a lock
    held across the sleep wedges every other thread for the full backoff.
    KB112's interprocedural lock stacks cover the TRANSITIVE
    sleep-under-lock case; this rule pins the lexical shapes:

    - ``while True`` + an exception handler that swallows-and-retries,
      with no attempt/deadline bound anywhere in the loop;
    - ``time.sleep`` inside a retry loop with no jitter term in the
      argument expression;
    - ``time.sleep`` inside a retry loop lexically under a ``with *lock``.
    """

    rule_id = "KB118"
    summary = ("serving-path retry loops: bounded attempts, jittered "
               "backoff, no time.sleep under a lock")

    _PACKAGES = ("kubebrain_tpu/backend/", "kubebrain_tpu/storage/",
                 "kubebrain_tpu/server/", "kubebrain_tpu/sched/",
                 "kubebrain_tpu/endpoint/", "kubebrain_tpu/lease/",
                 "kubebrain_tpu/faults/", "kubebrain_tpu/client.py")

    def applies(self, relpath: str) -> bool:
        p = relpath.replace("\\", "/")
        return any(p.startswith(pkg) for pkg in self._PACKAGES)

    def check(self, tree: ast.Module, src: str) -> Iterable[tuple[ast.AST, str]]:
        for loop in ast.walk(tree):
            if not isinstance(loop, (ast.While, ast.For)):
                continue
            swallowing = [
                h for node in walk_same_scope(loop.body)
                if isinstance(node, ast.Try)
                for h in node.handlers if _handler_swallows(h)
            ]
            if not swallowing:
                continue  # not a retry loop
            names = _loop_names(loop)
            bounded = (isinstance(loop, ast.For)  # for i in range(N): bounded
                       or any(_RETRY_BOUND_RE.search(n) for n in names)
                       or not _is_while_true(loop))
            if not bounded:
                yield loop, (
                    "unbounded `while True` retry loop (exception swallowed "
                    "and retried with no attempt cap or deadline); bound it "
                    "or escalate after K failures"
                )
            for call in _sleep_calls(loop.body):
                if _locks_enclosing(tree, call.lineno):
                    yield call, (
                        "time.sleep in a retry loop while holding a lock: "
                        "the backoff wedges every other thread on that lock "
                        "(transitive case: KB112)"
                    )
                elif not _sleep_has_jitter(call):
                    yield call, (
                        "retry backoff without jitter: a fleet of retriers "
                        "sleeping a constant re-collides forever; multiply "
                        "by random.uniform(0.5, 1.5) or similar"
                    )


#: the watch fan-out mask kernels (ops.fanout.fanout_mask* — prefix match,
#: E-major range, W-major range). Referencing one outside the two dispatch
#: funnels forks the packing discipline: a stray call site can silently
#: disagree on bound canonicalization (NUL single-key bounds), packed
#: width (the auto-grown table width), W/E padding, or the wat-mesh
#: sharding — the same drift KB109 fences for the scan kernels.
_FANOUT_MASK_PREFIX = "fanout_mask"
#: modules allowed to reference them: the legacy per-batch funnel (which
#: also defines them), the block-batched dispatch funnel, and the fused
#: multichip data-plane step (its own assembly point — the kernel runs
#: inside one shard_map'd step over the part x wat mesh)
_FANOUT_MASK_ALLOWED = (
    "kubebrain_tpu/ops/fanout.py",
    "kubebrain_tpu/fanout/dispatch.py",
    "kubebrain_tpu/parallel/step.py",
)


@register
class FanoutMaskOnlyInDispatchFunnels(Rule):
    """The fan-out mask kernels may only be referenced from the two
    dispatch funnels (`ops/fanout.py`, `fanout/dispatch.py`) — everything
    above (matcher, hub, backend) consumes masks or compacted index pairs,
    never launches the kernel itself (docs/watch.md). Imports count: an
    alias smuggled into another module is the same bypass as a call."""

    rule_id = "KB127"
    summary = ("fanout_mask* kernels may only be referenced from the "
               "dispatch funnels (ops/fanout.py, fanout/dispatch.py)")

    def applies(self, relpath: str) -> bool:
        p = relpath.replace("\\", "/")
        return p.startswith("kubebrain_tpu/") and p not in _FANOUT_MASK_ALLOWED

    def check(self, tree: ast.Module, src: str) -> Iterable[tuple[ast.AST, str]]:
        for node in ast.walk(tree):
            name = None
            if isinstance(node, (ast.Name, ast.Attribute)):
                name = terminal_name(node)
            elif isinstance(node, ast.ImportFrom):
                for a in node.names:
                    if a.name.startswith(_FANOUT_MASK_PREFIX):
                        name = a.name
                        break
            if name and name.startswith(_FANOUT_MASK_PREFIX):
                yield node, (
                    f"fan-out mask kernel reference {name!r}: the kernels "
                    "launch only from the dispatch funnels (ops/fanout.py, "
                    "fanout/dispatch.py); consume the matcher's masks or "
                    "compacted pairs instead"
                )
