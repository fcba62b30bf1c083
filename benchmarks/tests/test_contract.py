"""BENCHMARK.json against the files it names: everything a cell, a
configuration or a metric needs is found by name, so a later PR adds files
and entries and edits nothing — shown by doing so."""

import json
import os
import re
import shutil
import subprocess
import sys
from types import SimpleNamespace

import run
from conftest import BENCH

ROOT = os.path.dirname(BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    B = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
with open(os.path.join(BENCH, "tests", "history_cell.json")) as _f:
    #: a configuration with a history and a mix with kube-apiserver's
    #: Compact, as a ``model_config`` PR adds them: files and entries only
    HISTORY_CELL = json.load(_f)


def test_names_and_files():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for c in B["configs"]:
        assert NAME.match(c["name"]) and os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        assert set(c["reduced"]) == set(run.load_json(
            "configs", c["name"] + ".json")["reduced"])
    for w in B["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200 and w["chips"] == 1
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        traffic, config = run.cell_files(w)
        assert traffic["streams"] and config["tables"]
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"])
        spec = run.load_json("metrics", m["name"] + ".json")
        assert os.path.isfile(os.path.join(BENCH, "readers", spec["reader"] + ".py"))


def test_every_layer_metric_moves_a_metric_its_cells_report():
    cells = {w["name"] for w in B["workloads"]}
    reports = {m["name"]: set(m.get("workloads", cells)) for m in B["end_to_end"]}
    for m in B["per_layer"]:
        assert set(m["workloads"]) <= reports[m["moves"]], m["name"]
    for cell in cells:
        assert any(cell in r for n, r in reports.items() if n != "setup_s")
        assert any(cell in m["workloads"] for m in B["per_layer"])


def test_a_twin_is_its_bases_definition_under_the_name_of_another_cell():
    """A per-layer entry's cells have to report the end-to-end metric it
    moves. ``k8s-2500.relist-merge`` is a saturated cell (three closed-loop
    listers beside the whole write stream) and is judged on the rate it
    completes, ``ranges_per_s``: there the latencies swing with the machine
    by more than any bound allows (PERF.md section 2). So it reads every
    other metric under a twin name, ``<base>.beside``: the base's reader and
    arguments, per layer, ``moves`` ``ranges_per_s``, ``workloads`` the
    cells judged on ``ranges_per_s`` (every cell that is judged as it is,
    ``k8s-2500-h600.relist-compact`` too); the two judged latencies of the
    other cells among them. ``run.finish`` prints a metric for the cells of
    its own entry only (``test_rehearsal.py`` holds every cell's line to
    exactly that). No tail above the p95 and no quiet reading is an
    end-to-end metric."""
    e2e = {m["name"]: m for m in B["end_to_end"]}
    layer = {m["name"]: m for m in B["per_layer"]}
    judged = e2e["ranges_per_s"]["workloads"]
    assert "k8s-2500.relist-merge" in judged
    assert not set(e2e) & set(layer)
    assert len(e2e) + len(layer) == len(B["end_to_end"]) + len(B["per_layer"])
    twins = [n for n in layer if n.endswith(".beside")]
    assert len(twins) == 30
    for twin in twins:
        base = twin[:-len(".beside")]
        entry = layer.get(base) or e2e[base]
        a, b = (run.load_json("metrics", n + ".json") for n in (twin, base))
        assert (a["reader"], a.get("args")) == (b["reader"], b.get("args")), twin
        assert layer[twin]["workloads"] == judged, twin
        assert not set(judged) & set(entry["workloads"]), twin
        assert layer[twin]["moves"] == "ranges_per_s"
        assert {k: layer[twin][k] for k in ("unit", "better", "source")} == {
            k: entry[k] for k in ("unit", "better", "source")}
        assert layer[twin]["layer"] == entry.get("layer", "gRPC front")
    # what such a cell reads per layer is a twin, boot's phases, the Range
    # tail, or its own (a metric of that cell alone)
    shared = {"range_p99_ms", "boot_jax_init_s", "boot_store_open_s",
              "boot_mirror_build_s", "boot_compact_warm_s"}
    for cell in judged:
        mine = {n for n, m in layer.items() if cell in m["workloads"]}
        own = {n for n, m in layer.items() if m["workloads"] == [cell]}
        assert mine - set(twins) == shared | own, cell
        assert [n for n, m in e2e.items() if cell in m.get("workloads", [cell])] == [
            "ranges_per_s", "setup_s"]
    for m in B["end_to_end"]:
        args = run.load_json("metrics", m["name"] + ".json").get("args", {})
        assert "quiet" not in args and args.get("q", 0) < 99, m["name"]


def test_the_server_is_started_with_the_readme_flags_and_one_more():
    """No --merge-threshold, no --sched-*: the cells measure the defaults."""
    for c in B["configs"]:
        flags = run.load_json("configs", c["name"] + ".json")["server"]
        assert flags == ["--single-node", "--storage=tpu", "--inner-storage=native",
                         "--use-pallas", "--compact-interval", "86400"]


def test_a_new_metric_over_an_existing_reader_is_one_file_and_one_entry(tmp_path, monkeypatch):
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (bench / "metrics" / "txn_median_ms.json").write_text(json.dumps(
        {"reader": "client_percentile", "args": {"family": "txn", "q": 50}}))
    monkeypatch.setattr(run, "HERE", str(bench))
    ctx = SimpleNamespace(recs=lambda fam: [
        (1, "update", 0.0, 0.0, ms / 1e3, True) for ms in (1, 2, 3, 4, 5)])
    assert run.read_metric("txn_median_ms", ctx) == 3.0


def test_a_rate_metric_is_one_file_over_the_reader_that_is_kept_for_it(tmp_path, monkeypatch):
    """``write_ops_per_s`` left with the insert cell (PERF.md section 7); the
    overload cell that brings it back adds this one file."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (bench / "metrics" / "write_ops_per_s.json").write_text(json.dumps(
        {"reader": "ack_rate", "args": {"family": "txn"}}))
    monkeypatch.setattr(run, "HERE", str(bench))
    recs = [(1, "create", 0.0, 0.0, t, t < 9.5) for t in (1.0, 2.0, 9.0, 9.9, 12.0)]
    ctx = SimpleNamespace(recs=lambda fam, **kw: recs, window=(0.0, 10.0), window_s=10.0)
    assert run.read_metric("write_ops_per_s", ctx) == 0.3


def test_a_cell_a_configuration_and_a_metric_are_added_as_files_only(tmp_path):
    """A later PR's whole diff: one configuration file, one traffic file, one
    metric file, and their entries in BENCHMARK.json. No file that was there
    changes, and the new cell runs (here against the plain reference)."""
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    small = {"name": "tiny-kv", "reduced": [], "server": [], "tables": [{
        "name": "kv", "key": "/kubebrain/bench/{h}", "prefix": "/kubebrain/bench/",
        "ns_prefix": "/kubebrain/bench/", "hash_chars": 53, "count": 1500,
        "namespaces": 1, "value_bytes": {"dist": "fixed", "bytes": 512}}]}
    (tmp_path / "benchmarks" / "configs" / "tiny-kv.json").write_text(json.dumps(small))
    (tmp_path / "benchmarks" / "traffic" / "trickle.json").write_text(json.dumps({
        "why": "a later PR's mix", "warm_seconds": 1, "warmup_writes": 10,
        "merges_in_window": 0, "streams": [
            {"name": "w", "loop": "open", "rate": 64, "procs": 1, "judged": True,
             "ops": [{"op": "create", "table": "kv", "weight": 1}]}]}))
    (tmp_path / "benchmarks" / "metrics" / "txn_median_ms.json").write_text(json.dumps(
        {"reader": "client_percentile", "args": {"family": "txn", "q": 50}}))
    bench = json.loads(json.dumps(B))
    bench["configs"].append({"name": "tiny-kv", "source": "test", "reduced": [],
                             "file": "benchmarks/configs/tiny-kv.json", "why": "t"})
    bench["workloads"].append({"name": "tiny-kv.trickle", "config": "tiny-kv",
                               "traffic": "trickle", "chips": 1, "why": "t"})
    bench["end_to_end"].append({
        "name": "txn_median_ms", "unit": "ms", "better": "lower", "bound": 0.1,
        "source": "host_clock", "workloads": ["tiny-kv.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    out = subprocess.run(
        [sys.executable, str(tmp_path / "benchmarks" / "run.py"), "--workload",
         "tiny-kv.trickle", "--seed", str(2**31 + 77), "--seconds", "2",
         "--trace", "0", "--sut", "reference"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert RESULT_KEYS <= set(line) and list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"txn_median_ms", "setup_s"}
    # 64/s for 2 s: a gap that is exact in binary, so the schedule's sum of
    # gaps reaches the window's last instant exactly and leaves it out
    assert line["attempted"] == 128 and line["failed"] == 0
    assert line["rehearsal"]["comparison_passed"] and line["correct"] is False
    assert "merges in window: counted None, designed 0" in out.stdout


def test_a_history_and_a_compact_are_added_as_files_only(tmp_path):
    """The history and the compactor as a later PR adds them, rehearsed on
    the CPU backend through the whole run: the Compact is acknowledged, the
    reads at its revision C are the reference's, those at C - 1 refused, and
    the server's victims are the revisions etcd's rule removes; one merge
    for the Compact and none for the writes."""
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for program in ("kubebrain_tpu", "native"):
        (tmp_path / program).symlink_to(os.path.join(ROOT, program))
    (tmp_path / "benchmarks" / "configs" / "tiny-history.json").write_text(
        json.dumps(HISTORY_CELL["config"]))
    (tmp_path / "benchmarks" / "traffic" / "compacting.json").write_text(
        json.dumps(HISTORY_CELL["traffic"]))
    (tmp_path / "benchmarks" / "metrics" / "list_p95_ms.json").write_text(json.dumps(
        {"reader": "client_percentile", "args": {"family": "range", "q": 95}}))
    bench = json.loads(json.dumps(B))
    bench["configs"].append({"name": "tiny-history", "source": "test", "reduced": [],
                             "file": "benchmarks/configs/tiny-history.json", "why": "t"})
    bench["workloads"].append({"name": "tiny-history.compacting",
                               "config": "tiny-history", "traffic": "compacting",
                               "chips": 1, "why": "t"})
    bench["end_to_end"].append({
        "name": "list_p95_ms", "unit": "ms", "better": "lower", "bound": 0.2,
        "source": "host_clock", "workloads": ["tiny-history.compacting"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    out = subprocess.run(
        [sys.executable, str(tmp_path / "benchmarks" / "run.py"), "--workload",
         "tiny-history.compacting", "--seed", str(2**31 + 35), "--seconds", "4",
         "--trace", "0", "--sut", "cpu"],
        capture_output=True, text=True, timeout=600, cwd=tmp_path,
        env=dict(os.environ, JAX_COMPILATION_CACHE_DIR=os.path.join(ROOT, ".jax_cache")))
    assert out.returncode == 0, out.stderr[-4000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    numbers = line["compared"]
    for name in ("compact_refused", "compact_readback_wrong",
                 "compacted_reads_not_refused", "compact_victims_wrong"):
        assert numbers[name] == {"value": 0, "limit": 0}, (name, out.stderr[-3000:])
    for name in ("compared_compacts", "compared_compact_readback",
                 "compared_compacted_reads", "compared_compact_victims"):
        assert numbers[name]["value"] >= 1, name
    assert line["rehearsal"]["comparison_passed"], out.stderr[-3000:]
    assert line["failed"] == 0
    text = out.stdout
    assert "compaction: 1 ticks, 1 acknowledged" in text, text[-3000:]
    # the Compact's own merge, and no crossing: 1..1
    assert "merges in window: counted 1, designed 0 crossings" in text, text[-3000:]
