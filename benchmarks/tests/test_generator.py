"""The general generator gives the same operations for the same seed and
other ones for another seed, and every block of its schedule holds the same
set of operations."""

import json
import os

import worker
from conftest import BENCH


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


#: a configuration and a mix as a later PR would add them (upstream
#: KubeBrain's insert benchmark: 70 B keys with a random suffix)
KV_CONFIG = {"tables": [{
    "name": "kv", "key": "/kubebrain/bench/{h}", "prefix": "/kubebrain/bench/",
    "ns_prefix": "/kubebrain/bench/", "hash_chars": 53, "count": 40000,
    "namespaces": 1, "value_bytes": {"dist": "fixed", "bytes": 512}}]}
INSERT = {"name": "clients", "loop": "closed", "clients": 300, "ops": [
    {"op": "create", "table": "kv", "weight": 1}]}
PAGED = {"name": "lists", "loop": "open", "rate": 4, "ops": [
    {"op": "range_paged", "table": "pods", "page": 500, "weight": 1}]}


def _ops(seed: int, n: int = 300, mix: str = "steady", stream=0,
         config="k8s-2500", **more):
    """What the generator sends for one stream: the ``stream``-th of the
    mix's file, or a stream given as a dict."""
    if not isinstance(stream, dict):
        stream = _load("traffic", mix + ".json")["streams"][stream]
    if not isinstance(config, dict):
        config = _load("configs", config + ".json")
    config = json.loads(json.dumps(config))
    for t in config["tables"]:
        t["count"] = max(400, t["count"] // 100)
    spec = {"target": "127.0.0.1:1", "seed": seed, "worker": 0, "config": config,
            "stream": stream, "writers": 2, "writer": 1,
            "clients": 4, "rate": 100.0, **more}
    sent = []

    class Recording(worker.Traffic):
        def _send(self, rec, call, req):
            sent.append((rec.op, rec.key_id, rec.ver, req.SerializeToString()))
            if rec.family == worker.TXN:
                self.busy.discard(rec.req[1])

    gen = Recording(spec)
    gen.warming = "warmup_writes" in more
    for _ in range(n):
        gen.next_op(0.0)
    gen.stub.close()
    return sent


def test_same_seed_same_operations():
    assert _ops(2**31 + 5) == _ops(2**31 + 5)


def test_other_seed_other_operations():
    assert _ops(11) != _ops(12)


def test_every_block_holds_the_same_operations():
    """Seeds reorder the mix; they do not change it (27 = 25 + 1 + 1)."""
    for seed in (1, 2):
        kinds = [op for op, *_ in _ops(seed, 297)]
        for b in range(0, 297, 27):
            block = kinds[b:b + 27]
            assert (block.count("create"), block.count("delete")) == (1, 1)
            assert block.count("update") == 25


def test_a_writer_only_writes_its_own_keys():
    for _op, key_id, _ver, _req in _ops(3, 200):
        assert key_id % 2 == 1 or key_id >= 10_000_000 and (key_id - 10_000_000) % 2 == 1


def test_paged_lists_ask_for_pages():
    ops = _ops(4, 20, stream=PAGED)
    assert {op for op, *_ in ops} == {"range_paged"}
    assert all(b"\x18\xf4\x03" in req for *_x, req in ops)   # limit = 500


def test_a_new_operation_is_one_new_file(tmp_path, monkeypatch):
    """``ops/<op>.py`` is found by the name the workload file gives."""
    import shutil

    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (bench / "ops" / "get_first.py").write_text(
        "WRITES = False\nDEVICE_READ = False\n\n\n"
        "def issue(gen, op, pool, due):\n"
        "    gen.send_range(due, op['op'], pool['table'].key(0), end=b'')\n"
        "    return True\n")
    monkeypatch.setattr(worker, "HERE", str(bench))
    config = _load("configs", "k8s-2500.json")
    for t in config["tables"]:
        t["count"] = 400
    stream = {"name": "x", "loop": "closed", "ops": [
        {"op": "get_first", "table": "pods", "weight": 1}]}
    sent = []

    class Recording(worker.Traffic):
        def _send(self, rec, call, req):
            sent.append((rec.op, req.key))

    gen = Recording({"target": "127.0.0.1:1", "seed": 1, "worker": 0,
                     "config": config, "stream": stream, "writers": 1,
                     "writer": 0})
    gen.next_op(0.0)
    gen.stub.close()
    assert sent == [("get_first", b"/registry/pods/ns-000/pod-000000")]


def test_the_warm_up_sends_exactly_its_writes_and_then_none():
    """The merge-phase rule: the delta's fill as the window opens is a count."""
    ops = _ops(5, 300, warmup_writes=17)
    assert len(ops) == 17 and all(op in ("create", "update", "delete")
                                  for op, *_ in ops)


def test_an_informer_relists_one_namespace_and_then_the_next():
    reqs = [req for *_x, req in _ops(6, 5, mix="relist")]
    ns = [int(r.split(b"/registry/pods/ns-")[1][:3]) for r in reqs]
    assert [(b - a) % 25 for a, b in zip(ns, ns[1:])] == [1, 1, 1, 1]
    assert ns != [int(r.split(b"/registry/pods/ns-")[1][:3])
                  for *_x, r in _ops(7, 5, mix="relist")]


def test_upstream_keys_are_70_bytes_with_a_random_suffix():
    ops = _ops(8, 50, stream=INSERT, config=KV_CONFIG)
    keys = {worker.etcd.rpc_pb2.TxnRequest.FromString(req).compare[0].key
            for *_x, req in ops}
    assert len(keys) == 50 and {len(k) for k in keys} == {70}
    assert all(k.startswith(b"/kubebrain/bench/") for k in keys)
    assert len({k[17:19] for k in keys}) > 20      # spread over the keyspace
