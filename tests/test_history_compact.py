"""The device compaction against the benchmark's plain reference, on a store
with MVCC history, at a small size on the CPU backend.

A configuration's start state with its ``history`` (``benchmarks/state.py``:
every revision made from the seed) is loaded into the TPU engine, which then
compacts at the revision kube-apiserver's compactor would send first in the
configuration's mix (``State.head_at``: the head ``interval_s`` before the
tick). Held to ``State.at_many`` and ``check.Reference`` (the harness's
reference, which imports nothing of the program): every namespace Range and
every Count at C row for row, the reads at C - 1 refused, the victims by kind
those of etcd's rule, and a read at revision 0 the same before and after the
compacted mirror is swapped in. Both kernels (``jnp`` and the Pallas kernel
interpreted), two seeds, the harness's small configuration with a history and
the ``k8s-2500-h600`` deployment at ``--scale`` 0.01.
"""

import json
import os
import sys
import zlib

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run  # noqa: E402
from check import Reference  # noqa: E402
from state import State  # noqa: E402

from kubebrain_tpu import coder  # noqa: E402
from kubebrain_tpu.backend import Backend, BackendConfig  # noqa: E402
from kubebrain_tpu.backend.errors import CompactedError  # noqa: E402
from kubebrain_tpu.storage import new_storage  # noqa: E402


def _cell(name):
    """(configuration, its compactor's first tick: (due second, interval))."""
    if name == "tiny-history":
        with open(os.path.join(BENCH, "tests", "history_cell.json")) as f:
            cell = json.load(f)
        config, traffic, scale = cell["config"], cell["traffic"], 1.0
    else:
        config, traffic = (run.load_json("configs", name + ".json"),
                           run.load_json("traffic", "relist-compact.json"))
        scale = 0.01
    run.scale_tables(config, scale)
    s = next(s for s in traffic["streams"] if s["name"] == "compactor")
    interval = float(s["ops"][0]["interval_s"])
    return config, interval * float(s["phase"]), interval


class _Metrics:
    """The scanner's metrics sink, capturing the victims by kind."""

    def __init__(self):
        self.victims = {}

    def emit_counter(self, name, value=1, **tags):
        if name == "kb.compact.victims.total":
            self.victims[tags["kind"]] = self.victims.get(tags["kind"], 0) + value

    def emit_histogram(self, *a, **k):
        pass

    def emit_gauge(self, *a, **k):
        pass


def _load(backend, state):
    """The start state through the backend's group commit, as
    ``benchmarks/loader.py`` writes it, each revision the state's own."""
    ops, want = [], []

    def flush():
        for got, rev in zip(backend.write_batch(ops), want):
            assert (got[0] if isinstance(got, tuple) else got) == rev, got
        ops.clear()
        want.clear()

    for n, (verb, table, i, ver, guard) in enumerate(state.start_ops(), 1):
        key = table.key(i)
        if verb == "create":
            ops.append(("create", key, state.value(table, i, ver), None, 0))
        elif verb == "update":
            ops.append(("update", key, state.value(table, i, ver), guard, None, 0))
        else:
            ops.append(("delete", key, guard))
        want.append(n)
        if len(ops) == 256:
            flush()
    flush()
    assert backend.current_revision() == state.head_revision


def _rows(backend, start, end, revision):
    res = backend.list_(start, end, revision=revision)
    return [(kv.key, kv.revision, zlib.crc32(kv.value)) for kv in res.kvs]


def _ranges(state):
    """Every namespace of every table, and every table whole."""
    for t in state.tables.values():
        for ns in range(t.namespaces):
            p = t.ns_prefix(ns)
            yield p, coder.prefix_end(p)


CASES = [(c, k, s) for c in ("tiny-history", "k8s-2500-h600")
         for k in ("jnp", "pallas_interpret") for s in (2**31 + 36, 7)]


@pytest.fixture(scope="module", params=CASES, ids=lambda c: f"{c[0]}-{c[1]}-{c[2]}")
def compacted(request):
    name, kernel, seed = request.param
    os.environ["KB_PALLAS_INTERPRET"] = "1"
    config, due, interval = _cell(name)
    state = State(config, seed)
    target = state.head_at(state.history_seconds + due - interval)
    store = new_storage("tpu", inner="memkv", use_pallas=kernel != "jnp")
    backend = Backend(store, BackendConfig(event_ring_capacity=8192,
                                           watch_cache_capacity=4096))
    try:
        sc = backend.scanner
        assert sc._scan_kernel == kernel
        sc._host_limit_threshold = 0  # every read on the device path
        _load(backend, state)
        tables = [(t.prefix, coder.prefix_end(t.prefix))
                  for t in state.tables.values()]
        before = {r: _rows(backend, *r, 0) for r in tables}
        sc._metrics = metrics = _Metrics()
        assert backend.compact(target) == target
        sc._metrics = None
        assert sc.compact_count >= 1 and sc.compact_errors == 0
        assert sc._mirror_state == "serving"
        out = {"state": state, "target": target, "victims": metrics.victims,
               "before": before, "tables": tables, "backend": backend,
               "ref": Reference(state, [])}
        yield out
    finally:
        os.environ.pop("KB_PALLAS_INTERPRET", None)
        backend.close()
        store.close()


def test_ranges_and_counts_at_the_compact_revision(compacted):
    c = compacted
    ref, backend, target = c["ref"], c["backend"], c["target"]
    compared = 0
    for start, end in _ranges(c["state"]):
        want = ref.rows(start, end, target)
        assert _rows(backend, start, end, target) == want, start
        compared += len(want)
    for t in c["state"].tables.values():
        n, _rev = backend.count(t.prefix, coder.prefix_end(t.prefix), target)
        assert n == ref.count(t.prefix, target) == c["state"].live_count(
            t.name, target)
    assert compared > 0


def test_reads_below_the_compact_revision_refused(compacted):
    backend, target = compacted["backend"], compacted["target"]
    for start, end in compacted["tables"]:
        with pytest.raises(CompactedError):
            backend.list_(start, end, revision=target - 1)
        with pytest.raises(CompactedError):
            backend.count(start, end, target - 1)


def test_victims_by_kind_are_etcds(compacted):
    superseded, tombstones = compacted["ref"].removed(compacted["target"])
    got = compacted["victims"]
    assert superseded > 0 and tombstones > 0
    assert (got.get("superseded", 0), got.get("tombstone", 0)) == (
        superseded, tombstones)
    assert got.get("ttl_expired", 0) == 0


def test_revision_0_read_unchanged_by_the_swap(compacted):
    c = compacted
    head = c["state"].head_revision
    for r, before in c["before"].items():
        assert _rows(c["backend"], *r, 0) == before == c["ref"].rows(*r, head)
