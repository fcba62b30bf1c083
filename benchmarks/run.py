#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json`` through the served path.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json``'s entry for the cell names its configuration
(``configs/<config>.json``) and its traffic mix (``traffic/<mix>.json``);
every metric is read by the reader its file (``metrics/<name>.json``) names,
from ``readers/``; an operation of the traffic is ``ops/<op>.py``. Nothing
about a cell, a configuration, a metric or an operation is a table in this
file: a later PR adds files and entries, never edits (README.md).

A run: write the configuration's start state into a fresh native-store
directory (``loader.py``), start the README server on it as a child with
``JAX_PLATFORMS=tpu`` (``serve_child.py``), start the load generators
(``worker.py``), warm up with the cell's own traffic — its writes counted,
so the delta's fill is the same in every run as the window opens — measure
for ``--seconds``, drain, compare every answer with the reference
(``check.py``), reduce the trace (``tracered.py``), print.

The parent and the generators never import JAX; without a TPU the server
child fails to boot and the run exits non-zero with no result line. Other
systems under test are for rehearsal and calibration only and can never
print a line the driver would take for a result (``correct`` is false):
``--sut cpu`` (the same server on the CPU backend), ``--sut reference``
(the plain reference in the program's place, optionally with one guarantee
broken by ``--break``) and ``--sut hostpath`` (the program with its device
path switched off, ``--storage=native``: the control of the device account).
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pickle  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import urllib.request  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import check  # noqa: E402
import etcd  # noqa: E402
import mergephase  # noqa: E402
import plugin  # noqa: E402
import prom  # noqa: E402
import witness  # noqa: E402
from state import State  # noqa: E402

BOOT_TIMEOUT_S = 600.0
BOOT_PREFIX = "kubebrain-tpu boot: "
SENTINEL = b"~bench-sentinel"
SCRAPE_SLOP_S = 0.05


class RunFailure(RuntimeError):
    """The run cannot give a result; exit non-zero and print none."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(port: int, path: str, timeout: float = 120.0) -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=timeout) as r:
        return json.loads(r.read().decode())


def child_env(**extra: str) -> dict:
    """The parent's environment without the program's KB_* knobs, which
    would change what is measured."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("KB_")}
    env.update(extra)
    return env


# ------------------------------------------------------------------- server
class Server:
    """The system under test as a child process."""

    def __init__(self, sut: str, config: dict, config_path: str, seed: int,
                 data_dir: str, log_path: str, broken: str = ""):
        self.sut = sut
        self.client_port = free_port()
        self.info_port = self.probe_port = 0
        self.log_path = log_path
        self._log = open(log_path, "wb")
        if sut == "reference":
            argv = [sys.executable, os.path.join(HERE, "refserver.py"),
                    config_path, str(seed), str(self.client_port)]
            argv += ["--break", broken] if broken else []
            env = child_env()
        else:
            self.info_port, self.probe_port = free_port(), free_port()
            flags = list(config["server"])
            if sut == "hostpath":
                # the program's own host path: same store, no device mirror
                flags[flags.index("--storage=tpu")] = "--storage=native"
                flags = [f for f in flags if f not in ("--inner-storage=native",
                                                       "--use-pallas")]
            argv = [sys.executable, os.path.join(HERE, "serve_child.py"),
                    str(self.probe_port), *flags,
                    "--data-dir", data_dir, "--host", "127.0.0.1",
                    "--client-port", str(self.client_port),
                    "--peer-port", str(free_port()),
                    "--info-port", str(self.info_port)]
            # a busy or absent chip must be a boot failure, never a CPU server
            env = child_env(JAX_PLATFORMS="tpu" if sut == "chip" else "cpu")
            env.setdefault("JAX_COMPILATION_CACHE_DIR", cache_dir())
        self.proc = subprocess.Popen(argv, cwd=ROOT, env=env,
                                     stdout=self._log, stderr=self._log)

    @property
    def target(self) -> str:
        return f"127.0.0.1:{self.client_port}"

    def log_text(self) -> str:
        with open(self.log_path, "rb") as f:
            return f.read().decode(errors="replace")

    def wait_ready(self, probe_prefix: bytes) -> None:
        """Until one Count over a table is answered: the server's mirror
        rebuild from the store is paid here, before the clock of the
        window."""
        import grpc

        stub = etcd.Stub(self.target)
        deadline = time.monotonic() + BOOT_TIMEOUT_S
        try:
            while True:
                if self.proc.poll() is not None:
                    raise RunFailure(
                        f"the server exited rc={self.proc.returncode} before "
                        f"serving:\n{self.log_text()[-4000:]}")
                if time.monotonic() > deadline:
                    raise RunFailure("the server did not serve in "
                                     f"{BOOT_TIMEOUT_S:.0f}s")
                try:
                    stub.range(etcd.range_request(
                        probe_prefix, etcd.prefix_end(probe_prefix),
                        count_only=True), timeout=BOOT_TIMEOUT_S)
                    return
                except grpc.RpcError:
                    time.sleep(0.2)
        finally:
            stub.close()

    def boot_record(self) -> dict:
        for line in self.log_text().splitlines():
            if line.startswith(BOOT_PREFIX):
                return json.loads(line[len(BOOT_PREFIX):])
        return {}

    def stop(self) -> None:
        """Kill, not a graceful stop: the store's closing checkpoint would
        write the whole data set to disk once more for nothing."""
        if self.proc.poll() is None:
            self.proc.send_signal(
                signal.SIGTERM if self.sut == "reference" else signal.SIGKILL)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()


def cache_dir() -> str:
    """JAX's persistent compilation cache: where the environment says, else
    at a fixed path inside the checkout (the path is part of the key)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")


def cache_entries() -> int:
    path = cache_dir()
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# ------------------------------------------------------------------ workers
class Worker:
    def __init__(self, spec: dict, run_dir: str, name: str):
        self.name = name
        self.spec = dict(spec, out=os.path.join(run_dir, name + ".pkl"))
        spec_path = os.path.join(run_dir, name + ".json")
        with open(spec_path, "w") as f:
            json.dump(self.spec, f)
        self.err_path = os.path.join(run_dir, name + ".err")
        self._err = open(self.err_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "worker.py"), spec_path],
            cwd=ROOT, env=child_env(), stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=self._err, text=True)

    def expect(self, word: str, timeout: float) -> None:
        got: list[str] = []
        t = threading.Thread(
            target=lambda: got.append(self.proc.stdout.readline().strip()),
            daemon=True)
        t.start()
        t.join(timeout)
        if got != [word]:
            with open(self.err_path, "rb") as f:
                tail = f.read().decode(errors="replace")[-3000:]
            raise RunFailure(f"generator {self.name}: expected {word!r}, "
                             f"got {got} (rc={self.proc.poll()})\n{tail}")

    def tell(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def result(self) -> dict:
        # only bytes this benchmark's own generator wrote are unpickled
        with open(self.spec["out"], "rb") as f:
            return pickle.load(f)

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        for f in (self.proc.stdin, self.proc.stdout, self._err):
            f.close()


def plan_workers(workload: dict, config: dict, seed: int, target: str,
                 rate_scale: float) -> list[tuple[str, dict]]:
    """One spec per generator process. Writers split the keys between them
    by index, so no two ever write the same key."""
    streams = workload["streams"]
    n_writers = sum(int(s.get("procs", 1)) for s in streams
                    if mergephase.write_share(s))
    warm, warm_given = int(workload.get("warmup_writes", 0)), 0
    out, writer, index = [], 0, 0
    for s in streams:
        procs = int(s.get("procs", 1))
        writes_here = bool(mergephase.write_share(s))
        for p in range(procs):
            spec = {"target": target, "seed": seed, "worker": index,
                    "config": config, "stream": s, "writers": n_writers,
                    "writer": writer if writes_here else 0,
                    "sample_prob": s.get("sample_prob", 0.0)}
            if s.get("loop") == "closed":
                c = int(s["clients"])
                spec["clients"] = c // procs + (p < c % procs)
                wc = int(s.get("warm_clients", c))
                spec["warm_clients"] = wc // procs + (p < wc % procs)
            elif s.get("loop") == "open":
                spec["rate"] = float(s["rate"]) * rate_scale / procs
                spec["phase"] = (p + float(s.get("phase", 0.0))) / procs
            if writes_here:
                # the mix's warm-up writes, dealt evenly over its writers
                spec["warmup_writes"] = warm // n_writers + (
                    writer < warm % n_writers)
                warm_given += spec["warmup_writes"]
            out.append((f"{s['name']}-{p}", spec))
            writer += writes_here
            index += 1
    if warm_given != warm:
        raise RunFailure(f"{warm} warm-up writes and no stream that writes")
    return out


# ---------------------------------------------------------------------- run
def run_once(opts, workload: dict, config: dict, bench: dict,
             rate_scale: float = 1.0) -> dict:
    seed, seconds = int(opts.seed), float(opts.seconds)
    scale_tables(config, opts.scale)
    state = State(config, seed)
    run_dir = tempfile.mkdtemp(prefix="kb-bench-")
    data_dir = os.path.join(run_dir, "store")
    config_path = os.path.join(run_dir, "config.json")
    with open(config_path, "w") as f:
        json.dump(config, f)
    server = None
    workers: list[Worker] = []
    stub = None
    trace_thread = None
    machine = witness.Witness()
    on_server = opts.sut != "reference"
    try:
        t0 = time.monotonic()
        if on_server:
            load = subprocess.run(
                [sys.executable, os.path.join(HERE, "loader.py"), config_path,
                 str(seed), data_dir], cwd=ROOT, env=child_env(),
                capture_output=True, text=True)
            if load.returncode:
                raise RunFailure(f"loader failed:\n{load.stderr[-4000:]}")
            log(f"loaded: {load.stdout.strip()}")
        t_loaded = time.monotonic()
        server = Server(opts.sut, config, config_path, seed, data_dir,
                        os.path.join(run_dir, "server.log"), opts.broken)
        first = next(iter(state.tables.values()))
        server.wait_ready(first.prefix)
        t_serving = time.monotonic()
        device = {"platform": "reference", "kind": "none", "count": 0}
        if on_server:
            device = http_json(server.probe_port, "/device")
            log(f"server up: {server.boot_record()} device {device}")
            if opts.sut == "chip" and (
                    device.get("platform") != "tpu"
                    or device.get("count", 0) < int(opts.chips)):
                raise RunFailure(f"the cell asks for {opts.chips} TPU chip(s), "
                                 f"the server computes on {device}")

        for name, spec in plan_workers(workload, config, seed, server.target,
                                       rate_scale):
            workers.append(Worker(spec, run_dir, name))
        for w in workers:
            w.expect("ready", 300.0)
        begin = time.monotonic() + 0.2
        for w in workers:
            w.tell(f"start {begin!r}")

        # warm-up: the cell's own traffic. Its writes are COUNTED (exactly
        # ``warmup_writes``, then none: the delta's fill as the window
        # opens); its reads run on for ``warm_seconds`` and until nothing
        # compiles any more.
        for w in workers:
            w.expect("warmed", 300.0)
        warm_s = float(workload.get("warm_seconds", 5.0))
        quiet_since, entries = time.monotonic(), cache_entries()
        while True:
            time.sleep(0.25)
            now = time.monotonic()
            n = cache_entries()
            if n != entries:
                entries, quiet_since = n, now
            if now - begin >= warm_s and now - quiet_since >= min(3.0, warm_s):
                break
            if now - begin > 900.0:
                raise RunFailure("the warm-up never went quiet")
        # from before the window's first instant until every request that
        # was due in it is answered: this process only sleeps meanwhile
        machine.start()
        before = prom.scrape(server.info_port) if server.info_port else None
        t_before = time.monotonic()
        entries_t0 = cache_entries()
        win0 = time.monotonic() + 0.1
        win1 = win0 + seconds
        for w in workers:
            w.tell(f"window {win0!r} {win1!r}")
        setup_s = win0 - T_PROCESS
        log(f"window opens: load {t_loaded - t0:.1f}s, boot "
            f"{t_serving - t_loaded:.1f}s, warm-up {win0 - t_serving:.1f}s")

        capture: dict = {}
        if opts.trace and on_server:
            span = min(float(workload.get("trace_seconds", 3.0)), seconds / 2)

            def take():
                start = win0 + (seconds - span) / 2
                time.sleep(max(0.0, start - time.monotonic()))
                capture["scrapes"] = [prom.scrape(server.info_port)]
                capture.update(http_json(
                    server.probe_port,
                    "/profile/start?dir=" + os.path.join(run_dir, "trace")))
                if "error" in capture:
                    return
                time.sleep(max(0.0, start + span - time.monotonic()))
                capture.update(http_json(server.probe_port, "/profile/stop",
                                         300.0))
                capture["scrapes"].append(prom.scrape(server.info_port))
            trace_thread = threading.Thread(target=take, daemon=True)
            trace_thread.start()

        time.sleep(max(0.0, win1 - time.monotonic()))
        t_after = time.monotonic()
        after = prom.scrape(server.info_port) if server.info_port else None
        entries_t1 = cache_entries()
        if on_server:
            device = http_json(server.probe_port, "/device")

        # drain: every request that was due is waited for, and judged by
        # what it says
        watchers = [w for w in workers if "watch" in w.spec["stream"]]
        senders = [w for w in workers if w not in watchers]
        for w in senders:
            w.expect("done", 200.0)
        # stopped before this process does work of its own again (a long
        # unpickling would hold the witness's thread as a pause would)
        machine.stop()
        traffic = [w.result() for w in senders]
        bad_warm = sum(d["warm_failed"] + d["warm_unsent"] for d in traffic)
        if bad_warm:
            # judged by the comparison (a refused write is writes_refused);
            # the merges line below shows whether the phase held
            log(f"*** {bad_warm} warm-up writes failed or were never sent: "
                "the delta's fill as the window opened is not the design's")
        drained = prom.scrape(server.info_port) if server.info_port else None
        stub = etcd.Stub(server.target)
        readback = check.read_back(stub, etcd, state, traffic, seed)
        if trace_thread is not None:
            trace_thread.join(300.0)
        sentinels: dict[str, int] = {}
        watch_dumps = []
        if watchers:
            tables = sorted({w["table"] for wk in watchers
                             for w in wk.spec["stream"]["watch"]})
            for name in tables:
                key = state.tables[name].prefix + SENTINEL
                resp = stub.txn(etcd.put_txn(key, b"end", 0), timeout=60.0)
                sentinels[name] = etcd.txn_revision(resp)
            for w in watchers:
                w.tell("finish " + " ".join(str(sentinels[t]) for t in tables))
            for w in watchers:
                w.expect("done", 200.0)
                watch_dumps.append(w.result())
        final = prom.scrape(server.info_port) if server.info_port else None
        server.stop()
        if opts.keep_trace and on_server:
            os.makedirs(opts.keep_trace, exist_ok=True)
            shutil.copy(server.log_path, opts.keep_trace)

        ctx = Context(opts, workload, config, state, traffic, watch_dumps,
                      (win0, win1), setup_s, before, after,
                      entries_t1 - entries_t0, device, rate_scale, machine)
        ctx.drained, ctx.compaction = drained, readback.get("compaction")
        if capture:
            log("capture: " + str({k: v for k, v in capture.items()
                                   if k != "scrapes"}))
        if capture.get("stop"):
            if opts.keep_trace:
                shutil.copy(find_file(capture["dir"], ".xplane.pb"),
                            opts.keep_trace)
            try:
                ctx.trace = reduce_trace(capture["dir"])
                ctx.trace["window_s"] = capture["stop"] - capture["start"]
                ctx.trace["scrapes"] = capture["scrapes"]
            except RunFailure as e:
                if opts.sut == "chip":
                    raise
                log(f"rehearsal: no device plane in a CPU trace ({e})"[:300])
        elif opts.trace and on_server:
            raise RunFailure(f"no trace was captured: {capture}")
        account = None
        if before is not None:
            ops_dir = os.path.join(HERE, "ops")
            # where the mix compacts, only the dispatches of reads count
            rpc = check.READ_RPC if mergephase.compacts(
                workload, seconds, rate_scale) else None
            account = {
                "not_serving": sum(
                    prom.series_sum(s, "kb_mirror_state", state="serving") != 1.0
                    for s in (before, after, final)),
                # the requests, sent and answered between the window's two
                # scrapes (answered with room to spare: the server may count
                # a stage a moment after the client has its reply), whose op
                # says of itself that the device answers it
                "device_reads": sum(
                    1 for r in ctx.recs(0, judged_only=False, due_in_window=False)
                    if r[5] and t_before <= r[3] and r[4] <= t_after - SCRAPE_SLOP_S
                    and plugin.load(ops_dir, r[1]).DEVICE_READ),
                "window": check.device_account(after, before, rpc),
                "readback": check.device_account(final, drained, rpc),
                # the Compacts' victims by etcd's rule (a TTL's and the
                # revision records' are another matter)
                "compact_victims": sum(prom.delta(
                    drained, before, "kb_compact_victims_total", kind=k)
                    for k in ("superseded", "tombstone"))}
            log(f"device account: window {account['device_reads']} reads, "
                f"{account['window']}; read-back {readback['device_reads']} "
                f"reads, {account['readback']}")
        numbers = check.compare(state, traffic, watch_dumps, readback,
                                sentinels, account)
        return finish(ctx, bench, numbers)
    except BaseException:
        if server is not None and on_server:
            sys.stderr.write(f"---- server log (tail) ----\n"
                             f"{server.log_text()[-6000:]}\n")
        raise
    finally:
        machine.stop()
        if stub is not None:
            stub.close()
        for w in workers:
            w.stop()
        if server is not None:
            server.stop()
        shutil.rmtree(run_dir, ignore_errors=True)


def scale_tables(config: dict, scale: float) -> None:
    """``--scale`` shrinks every table for a rehearsal, and the rates of its
    history with it (the history's seconds stay, so that a Compact's target
    still lies inside it); a cell runs at 1."""
    if scale != 1.0:
        for t in config["tables"]:
            t["count"] = max(t.get("namespaces", 1) * 4, int(t["count"] * scale))
            if "history" in t:
                t["history"] = {k: v * scale for k, v in t["history"].items()}


def find_file(root: str, suffix: str) -> str:
    for base, _dirs, files in os.walk(root):
        for f in files:
            if f.endswith(suffix):
                return os.path.join(base, f)
    raise RunFailure(f"no *{suffix} under {root}")


def reduce_trace(trace_dir: str) -> dict:
    """In a process of its own: reading the trace needs JAX, and the parent
    stays off it."""
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "tracered.py"), trace_dir],
        cwd=ROOT, env=child_env(JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    if out.returncode:
        raise RunFailure(f"trace reduction failed:\n{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


# ------------------------------------------------------------------ metrics
class Context:
    """What a metric's reader may read."""

    def __init__(self, opts, workload, config, state, traffic, watches, window,
                 setup_s, before, after, cache_growth, device, rate_scale=1.0,
                 machine=None):
        self.opts, self.workload, self.config, self.state = (
            opts, workload, config, state)
        self.traffic, self.watches, self.window = traffic, watches, window
        self.window_s = window[1] - window[0]
        self.setup_s, self.before, self.after = setup_s, before, after
        self.cache_growth, self.device = cache_growth, device
        self.rate_scale = rate_scale
        self.machine = machine or witness.Witness()
        self.trace: dict | None = None
        # the scrape once every generator has drained, and the read-back
        # after a Compact (``check.read_back_compacted``)
        self.drained: dict | None = None
        self.compaction: dict | None = None
        # rows the mirror holds: the start state's plus every write so far
        self.mirror_rows = state.rows + sum(
            1 for d in traffic for r in d["recs"] if r[0] == 1 and r[5])

    def recs(self, family: int | None = None, judged_only: bool = True,
             loop: str | None = None, due_in_window: bool = True):
        """The window's requests: those that were due inside it (or, with
        ``due_in_window`` off, every request of the run)."""
        lo, hi = self.window
        for dump in self.traffic:
            if judged_only and not dump["judged"]:
                continue
            if loop and dump["loop"] != loop:
                continue
            for r in dump["recs"]:
                if (family is None or r[0] == family) and (
                        not due_in_window or lo <= r[2] < hi):
                    yield r

    def watch_pairs(self):
        """(the write's due time, the event's arrival) for every (event,
        watcher) pair of the window's acknowledged writes."""
        due = {r[6]: r[2] for r in self.recs(1, judged_only=False) if r[5]}
        for dump in self.watches:
            for w in dump["watches"]:
                for ev in w["events"]:
                    if ev[0] in due:
                        yield due[ev[0]], ev[4]

    def touched(self, a: float, b: float) -> bool:
        """The pause rule, stated once for every reader: was the machine
        witnessed pausing (or draining a pause's burst) anywhere in ``[a,
        b]``, a request's due time and its answer's arrival?"""
        return witness.touched(self.machine.pauses, a, b)

    def merges(self):
        """(counted, (fewest, most) crossings designed, most merges the rule
        allows with the readers' follow-ups: ``mergephase.expected``) for the
        window's delta merges; counted is None without a ``/metrics`` to
        read."""
        counted = None if self.before is None else int(prom.delta(
            self.after, self.before, "kb_mirror_merge_seconds_count"))
        args = (self.workload, self.window_s, self.rate_scale)
        return counted, mergephase.crossings(*args), mergephase.expected(*args)[1]

    def worst_by_second(self, family: int) -> list[float]:
        """The worst latency (ms) of the family's requests due in each
        whole second of the window, 0 for a second with none; empty where
        the window holds none."""
        by_second: dict[int, float] = {}
        for r in self.recs(family, judged_only=False):
            sec = int(r[2] - self.window[0])
            by_second[sec] = max(by_second.get(sec, 0.0), (r[4] - r[2]) * 1e3)
        if not by_second:
            return []
        return [by_second.get(s, 0.0) for s in range(int(self.window_s))]


def read_metric(name: str, ctx: Context):
    """The metric's value by the reader its file names; None where the
    reader finds nothing to read."""
    spec = load_json("metrics", name + ".json")
    reader = plugin.load(os.path.join(HERE, "readers"), spec["reader"])
    return reader.read(ctx, **spec.get("args", {}))


def finish(ctx: Context, bench: dict, numbers: dict) -> dict:
    cell = ctx.opts.workload
    kind = "per_layer" if ctx.opts.trace else "end_to_end"
    metrics = {}
    for m in bench[kind]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        value = read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted = failed = 0
    for r in ctx.recs(judged_only=False):
        attempted += 1
        failed += not r[5]
    on_chip = ctx.opts.sut == "chip"
    result = {
        # a rehearsal or a control is never a result
        "correct": bool(check.verdict(numbers)) and on_chip,
        "attempted": attempted, "failed": failed, "metrics": metrics,
        "device": dict(ctx.device)}
    if not on_chip:
        result["rehearsal"] = {"sut": ctx.opts.sut, "break": ctx.opts.broken,
                               "comparison_passed": check.verdict(numbers)}
    if ctx.trace is not None:
        result["device"]["busy_s"] = ctx.trace["busy_s"]
        result["device"]["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {
            "device_ops": [[n[:200], s] for n, s, _c in ctx.trace["ops"][:10]],
            "idle_gaps": ctx.trace["idle_gaps"][:10]}
    # each number compared beside its limit: the line's last key
    result["compared"] = {
        k: {kk: v[kk] for kk in ("value", "limit")} for k, v in numbers.items()}
    return {"result": result, "numbers": numbers, "ctx": ctx}


def summary_lines(ctx: Context) -> list[str]:
    """The earlier lines: each judged family's tails over every request and,
    beside them, over those no witnessed pause of the machine touched (the
    pause rule), failures by error string, the per-second worst Txn,
    generator lateness, the machine's pauses, the compile cache's growth, the
    merges counted beside the designed crossings and the follow-ups allowed,
    and each crossing's stall and backlog beside the design's allowance."""
    from stats import percentile

    def tails(lat):
        return (f"p50={percentile(lat, 50):.2f}ms p75={percentile(lat, 75):.2f}ms "
                f"p95={percentile(lat, 95):.2f}ms p99={percentile(lat, 99):.2f}ms "
                f"max={max(lat):.2f}ms")

    def both(label, pairs, rate=""):
        """One family's line: every request's reading (what is judged) and,
        beside it, the reading without those a pause touched."""
        lat = [(b - a) * 1e3 for a, b in pairs]
        quiet = [(b - a) * 1e3 for a, b in pairs if not ctx.touched(a, b)]
        return (f"{label}: n={len(lat)}{rate} {tails(lat)}; touched by a pause "
                f"{len(lat) - len(quiet)}; untouched "
                + (tails(quiet) if quiet else "nothing"))

    out = []
    for fam, label in ((0, "range"), (1, "txn")):
        for judged in (True, False):
            pairs = [(r[2], r[4]) for d in ctx.traffic
                     if d["judged"] == judged for r in d["recs"]
                     if r[0] == fam and r[5] and ctx.window[0] <= r[2] < ctx.window[1]]
            if pairs and judged:
                out.append(both(label, pairs,
                                f" {len(pairs) / ctx.window_s:.1f}/s"))
            elif pairs:
                lat = [(b - a) * 1e3 for a, b in pairs]
                out.append(f"{label} (background): n={len(lat)} {tails(lat)} "
                           f"{len(lat) / ctx.window_s:.1f}/s")
    pairs = list(ctx.watch_pairs())
    if pairs:
        out.append(both("watch lag (event, watcher) pairs", pairs))
    mid = (ctx.window[0] + ctx.window[1]) / 2
    halves = [[(r[4] - r[2]) * 1e3 for r in ctx.recs(1, loop="open") if r[5]
               and (r[2] < mid) == first] for first in (True, False)]
    if all(halves):
        # a backlog that grows through the window shows as a later half
        # slower than the earlier
        out.append(f"open-loop txn p50 by half of the window: "
                   f"{percentile(halves[0], 50):.2f}ms then "
                   f"{percentile(halves[1], 50):.2f}ms")
    errors: dict[str, int] = {}
    for d in ctx.traffic:
        for r in d["recs"]:
            if not r[5]:
                errors[r[10][:80]] = errors.get(r[10][:80], 0) + 1
    out.append(f"failed requests by error: {errors or 'none'}")
    for fam, label in ((1, "txn"), (0, "range")):
        worst = ctx.worst_by_second(fam)
        if worst:
            # a stall (a delta merge, a compaction) shows as a run of seconds
            out.append(f"{label} max latency by second of the window (ms): "
                       + " ".join(f"{ms:.0f}" for ms in worst))
    late = [(r[3] - r[2]) * 1e3 for r in ctx.recs(loop="open", judged_only=False)]
    if late:
        out.append(f"generator lateness (open loops): p50={percentile(late, 50):.3f}ms "
                   f"p95={percentile(late, 95):.3f}ms max={max(late):.3f}ms")
    out.append(ctx.machine.line(ctx.window))
    out.append(f"compile cache: +{ctx.cache_growth} entries in the window")
    counted, (lo, hi), most = ctx.merges()
    rate = mergephase.write_rate(ctx.workload, ctx.rate_scale)
    readers = mergephase.followup_readers(ctx.workload)
    designed = str(lo) if lo == hi else f"{lo}..{hi}"
    line = (f"merges in window: counted {counted}, designed {designed} "
            f"crossings, up to {hi} x {readers} reader follow-ups "
            f"(T={mergephase.MERGE_THRESHOLD}, warm-up writes "
            f"{ctx.workload.get('warmup_writes', 0)}, writes/s "
            f"{rate[0]:g}" + (f"..{rate[1]:g}" if rate[1] != rate[0] else "")
            + f", window {ctx.window_s:g} s)")
    # each designed crossing's stall and backlog as the writers saw it
    stalls = mergephase.stalls(ctx.worst_by_second(1), mergephase.crossing_times(
        ctx.workload, ctx.window_s, ctx.rate_scale))
    allowance = mergephase.merge_stall_s(ctx.workload)
    if stalls:
        line += (f"; stall and backlog after each crossing (s): "
                 + " ".join(f"{x:.2f}" for x in stalls)
                 + f" (allowance {allowance:g} s)")
    if counted is not None and not lo <= counted <= most:
        line += "  *** MERGE PHASE OFF THE DESIGN: this run measured another cell ***"
    if stalls and max(stalls) > allowance:
        line += "  *** MERGE STALL OVER THE DESIGN'S ALLOWANCE ***"
    out.append(line)
    ticks = list(ctx.recs(check.COMPACT, judged_only=False, due_in_window=False))
    if ticks:
        out.append(compaction_line(ctx, ticks))
    return out


def compaction_line(ctx: Context, ticks: list) -> str:
    """Where the mix compacts: the compactor's ticks, the target C, the
    victims by kind (the server's beside the reference's) and the pass's
    phases."""
    back = ctx.compaction or {"target": None, "removed": None}
    took = " ".join(f"{(r[4] - r[2]) * 1e3:.0f}" for r in ticks)
    line = (f"compaction: {len(ticks)} ticks, {sum(r[5] for r in ticks)} "
            f"acknowledged, due -> answered (ms) {took}; target "
            f"{back['target']} (start state's head {ctx.state.head_revision})")
    ref = back["removed"]
    if ref is not None:
        line += (f"; reference removes superseded={ref[0]} tombstone={ref[1]}"
                 f" of {ctx.mirror_rows} rows")
    if ctx.drained is not None:
        kinds = ("superseded", "tombstone", "ttl_expired", "rev_record")
        victims = " ".join(f"{k}={prom.delta(ctx.drained, ctx.before, 'kb_compact_victims_total', kind=k):.0f}"
                           for k in kinds)
        phases = " ".join(f"{ph}={1e3 * prom.delta(ctx.drained, ctx.before, 'kb_compact_seconds_sum', phase=ph):.1f}ms"
                          for ph in ("mark", "gc", "merge", "publish"))
        passes = prom.delta(ctx.drained, ctx.before, "kb_compact_seconds_count",
                            phase="mark")
        line += f"; server victims {victims}; {passes:.0f} passes: {phases}"
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sut", choices=("chip", "cpu", "reference", "hostpath"),
                    default="chip", help="all but chip: rehearsal and "
                    "calibration only, never a result")
    ap.add_argument("--break", dest="broken", default="",
                    help="with --sut reference: the guarantee to break")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="rehearsal only: shrink every table")
    ap.add_argument("--sweep", default="",
                    help="comma-separated factors on every open loop's rate; "
                    "one run each, one summary line each, no result line")
    ap.add_argument("--keep-trace", default="",
                    help="copy the run's .xplane.pb into this directory")
    opts = ap.parse_args(argv)
    if opts.scale != 1.0 and opts.sut == "chip":
        ap.error("--scale is for rehearsals (--sut cpu or reference)")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if opts.workload not in cells:
        ap.error(f"BENCHMARK.json has no cell {opts.workload!r}")
    cell = cells[opts.workload]
    opts.chips = cell["chips"]
    try:
        if opts.sweep:
            for factor in [float(x) for x in opts.sweep.split(",")]:
                out = run_once(opts, *cell_files(cell), bench, factor)
                r = out["result"]
                print(json.dumps({
                    "rate_scale": factor, "attempted": r["attempted"],
                    "failed": r["failed"], "comparison_passed":
                    check.verdict(out["numbers"]), "metrics": r["metrics"],
                    "summary": summary_lines(out["ctx"])}), flush=True)
            return 0
        out = run_once(opts, *cell_files(cell), bench)
    except RunFailure as e:
        log(f"FAILED: {e}")
        return 1
    if "jax" in sys.modules:
        log("FAILED: the benchmark's parent imported jax")
        return 1
    result = out["result"]
    for line in summary_lines(out["ctx"]):
        print(line, flush=True)
    for name, n in out["numbers"].items():
        print(f"compared {name}: {n['value']} (limit {n['op']} {n['limit']})"
              + (f" -- {n['first']}" if n.get("first") else ""),
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def cell_files(cell: dict) -> tuple[dict, dict]:
    """(traffic mix, configuration) of one ``workloads`` entry, each found
    by the name the entry gives."""
    return (load_json("traffic", cell["traffic"] + ".json"),
            load_json("configs", cell["config"] + ".json"))


if __name__ == "__main__":
    sys.exit(main())
