"""North-star benchmark: MVCC range-scan rate over a 1M-key x 100-revision
class dataset (BASELINE.json config: "range-scan keys/sec").

Measures the device visibility kernel (prefix-match + revision filter +
last-version select + tombstone suppression — the single pass the reference
does row-by-row in scanner worker.run, scanner.go:389-516) over HBM-resident
packed blocks, against a vectorized numpy CPU implementation of the *same*
algorithm (a much stronger baseline than the reference's per-row LSM
iteration).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Env knobs: KB_BENCH_KEYS (default 200000), KB_BENCH_REVS (default 100),
KB_BENCH_PLATFORM (force "cpu"), KB_BENCH_ITERS.
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import time

import numpy as np

WIDTH = 64  # bytes per packed key; registry bench keys are ~36B
CHUNKS = WIDTH // 4


def platform_info() -> dict:
    """Platform/device stamp carried by EVERY emitted bench JSON: acceptance
    bars differ by device class (the PR 5 batched bar is TPU-only), so each
    record must say where it ran instead of leaving that to stderr logs.
    Stamps the backend THIS process computed on, and never initializes one
    just for the stamp: the modes that spawn servers keep this process off
    the device (one process owns a chip), and their servers stamp their own
    platform (workload reports read it off the server's /metrics)."""
    jax = sys.modules.get("jax")
    if jax is not None and jax._src.xla_bridge.backends_are_initialized():
        dev = jax.devices()[0]  # backend already live: this is cheap
        return {"platform": dev.platform, "device": str(dev)}
    return {"platform": "host", "device": "host(jax backend not initialized)"}


def build_dataset(n_keys: int, revs_per_key: int):
    """Vectorized construction of sorted (key, rev) rows: fixed-format keys
    '/registry/pods/default/pod-%08d' x revs_per_key ascending revisions,
    last version tombstoned for 10% of keys."""
    prefix = b"/registry/pods/default/pod-"
    plen = len(prefix)
    n = n_keys * revs_per_key

    from kubebrain_tpu.ops import keys as keyops

    digits = np.zeros((n_keys, 8), np.uint8)
    x = np.arange(n_keys, dtype=np.int64)
    for d in range(7, -1, -1):
        digits[:, d] = (x % 10) + ord("0")
        x //= 10
    key_bytes = np.zeros((n_keys, WIDTH), np.uint8)
    key_bytes[:, :plen] = np.frombuffer(prefix, np.uint8)
    key_bytes[:, plen : plen + 8] = digits

    chunks = keyops.bytes_to_chunks(np.repeat(key_bytes, revs_per_key, axis=0))

    revs = np.arange(1, n + 1, dtype=np.uint64)
    rh = (revs >> np.uint64(32)).astype(np.uint32)
    rl = (revs & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    tomb = np.zeros(n, dtype=bool)
    tomb[revs_per_key - 1 :: 10 * revs_per_key] = True  # last version of every 10th key
    return chunks, rh, rl, tomb


def pack_bound(key: bytes) -> np.ndarray:
    from kubebrain_tpu.ops import keys as keyops

    return keyops.pack_one(key, WIDTH)


def key_encoding_info(chunks: np.ndarray, sample: int = 200_000) -> dict:
    """Schema-stamped mirror-compression stats for a (sorted) packed-key
    dataset: what the serving mirror would store per row under the
    order-preserving prefix/dictionary encoding (docs/compression.md) —
    the capacity-unlock fields BENCH/MULTICHIP JSONs track across rounds."""
    from kubebrain_tpu.ops import keys as keyops
    from kubebrain_tpu.storage.tpu.encode import build_encoding

    stride = max(1, len(chunks) // sample)
    u8 = keyops.chunks_to_u8(np.asarray(chunks[::stride]))
    w = u8.shape[1]
    nz = (u8[:, ::-1] != 0).argmax(axis=1)
    lens = np.where((u8 != 0).any(axis=1), w - nz, 0).astype(np.int64)
    enc = build_encoding(u8, lens, raw_width=w)
    enc_w = enc.width if enc is not None else w
    # per-row device bytes: key column + rev hi/lo (8B) + tomb/ttl flags (2B)
    return {
        "schema": "kubebrain-keyenc/v1",
        "raw_key_bytes_per_row": w,
        "encoded_key_bytes_per_row": enc_w,
        "mirror_bytes_per_row": enc_w + 10,
        "raw_mirror_bytes_per_row": w + 10,
        "key_compression_ratio": round(w / enc_w, 3),
        "dict_entries": len(enc.boundaries) if enc is not None else 0,
    }


def cpu_scan(chunks, rh, rl, tomb, start, end, qhi, qlo) -> int:
    """The same visibility algorithm, vectorized numpy (CPU baseline)."""
    def lex_less(keys, bound):
        eq = keys == bound
        neq = ~eq
        has_diff = neq.any(axis=1)
        first = neq.argmax(axis=1)
        lt_first = np.take_along_axis(keys < bound, first[:, None], axis=1)[:, 0]
        return has_diff & lt_first

    in_range = ~lex_less(chunks, start) & lex_less(chunks, end)
    rev_le = (rh < qhi) | ((rh == qhi) & (rl <= qlo))
    cand = in_range & rev_le
    same_next = np.zeros(len(chunks), dtype=bool)
    same_next[:-1] = (chunks[1:] == chunks[:-1]).all(axis=1)
    cand_next = np.zeros_like(cand)
    cand_next[:-1] = cand[1:]
    visible = cand & ~(same_next & cand_next) & ~tomb
    return int(visible.sum())


def _fanout_population(n_watchers: int, n_broad: int, rng):
    """Kube-realistic watcher specs ``[(wid, start, end, min_rev)]``:
    namespace/kind prefix ranges (the informer shape), ~2% single-key
    watches whose end bound carries a NUL (``key + b"\\0"``), and
    ``n_broad`` broad unbounded watches over the whole registry. The broad
    cohort makes the hub's ``_RangeIndex`` go DENSE, which is exactly the
    population class that routes ``stream`` to the device block path even
    on CPU backends."""
    kinds = (b"pods", b"leases", b"endpoints", b"configmaps")
    namespaces = [b"ns-%03d" % i for i in range(40)]
    specs = []
    for w in range(n_watchers - n_broad):
        ns = namespaces[rng.randint(len(namespaces))]
        kind = kinds[rng.randint(len(kinds))]
        if rng.rand() < 0.02:
            # single-key watch: end = key + NUL (etcd single-key range)
            key = b"/registry/%s/%s/obj-%05d" % (kind, ns, rng.randint(4096))
            specs.append((w, key, key + b"\x00", int(rng.randint(0, 256))))
        else:
            start = b"/registry/%s/%s/" % (kind, ns)
            end = start[:-1] + bytes([start[-1] + 1])
            specs.append((w, start, end, int(rng.randint(0, 256))))
    for b in range(n_broad):
        specs.append((n_watchers - n_broad + b, b"/registry/", b"", 0))
    return specs


def _fanout_events(n_events: int, rev0: int, rng, ts: float = 0.0):
    from kubebrain_tpu.backend.common import WatchEvent

    kinds = (b"pods", b"leases", b"endpoints", b"configmaps")
    namespaces = [b"ns-%03d" % i for i in range(40)]
    return [
        WatchEvent(
            revision=rev0 + i,
            key=b"/registry/%s/%s/obj-%05d" % (
                kinds[rng.randint(len(kinds))],
                namespaces[rng.randint(len(namespaces))],
                rng.randint(4096)),
            value=b"v",
            ts=ts,
        )
        for i in range(n_events)
    ]


def _next_fanout_path(root: str) -> str:
    import re

    pat = re.compile(r"FANOUT_r(\d+)\.json$")
    rounds = [int(m.group(1)) for f in os.listdir(root) if (m := pat.match(f))]
    return os.path.join(root, "FANOUT_r%02d.json" % (max(rounds, default=0) + 1))


def bench_fanout() -> None:
    """Watch fan-out bench (make bench-fanout; docs/watch.md): block-batched
    device matching at 10k watchers, three legs —

    - **identity**: the device matcher's delivery masks byte-identical to
      the brute-force raw-bytes oracle (full W, leading events) AND its
      block deliveries identical to the host segment-index
      (``_RangeIndex``) oracle over the index-buildable sub-population;
    - **throughput**: one block dispatch for the whole drain
      (``DeviceFanout.deliver``) vs the per-batch legacy device path
      (EVENT_BATCH-chunked ``FanoutMatcher`` masks + hub-style column
      demux) — the batched path must be >= 2x on CPU-sim; the TPU bar is
      the same 2x asserted on-TPU and stamped pending_tpu off it;
    - **lag**: the same population subscribed on a REAL WatcherHub with
      PrometheusMetrics armed; drain blocks stream through the hub's
      device block route and p99 of ``kb_watch_lag_seconds{point=queue}``
      must land under KB_FANOUT_LAG_BOUND_S.

    Report: FANOUT_rNN.json (kubebrain-fanout/v1) in the repo root, or
    KB_FANOUT_OUT. Perf bars are asserted AFTER the report is emitted."""
    import time as _time

    import jax

    from kubebrain_tpu.backend.watcherhub import WatcherHub, _RangeIndex
    from kubebrain_tpu.fanout.matcher import DeviceFanout, match_oracle
    from kubebrain_tpu.metrics.prom import PrometheusMetrics
    from kubebrain_tpu.ops.fanout import FanoutMatcher
    from kubebrain_tpu.workload import slo

    n_watchers = int(os.environ.get("KB_BENCH_WATCHERS", 10_000))
    n_events = int(os.environ.get("KB_BENCH_EVENTS", 512))
    n_broad = int(os.environ.get("KB_BENCH_BROAD", 100))
    iters = int(os.environ.get("KB_BENCH_ITERS", 3))
    rounds = int(os.environ.get("KB_BENCH_ROUNDS", 4))
    lag_bound = float(os.environ.get("KB_FANOUT_LAG_BOUND_S", 5.0))
    rng = np.random.RandomState(0)

    specs = _fanout_population(n_watchers, n_broad, rng)
    events = _fanout_events(n_events, rev0=300, rng=rng)

    # ---- leg 1: identity ------------------------------------------------
    matcher = DeviceFanout()
    # brute-force raw-bytes oracle over the FULL watcher population on the
    # leading events (bounded: the oracle is O(E*W) Python)
    n_oracle_ev = min(n_events, 64)
    mask_dev = matcher(events[:n_oracle_ev], specs, version=1)
    mask_brute = match_oracle(events[:n_oracle_ev], specs)
    assert (mask_dev == mask_brute).all(), "device mask diverged from oracle"
    # segment-index oracle over the index-buildable (bounded) population,
    # against the BLOCK protocol's demuxed deliveries, all events
    narrow = [s for s in specs if s[2]]
    filters = {wid: (s, e, r) for wid, s, e, r in narrow}
    index = _RangeIndex(filters)
    assert not index.dense, "bounded sub-population unexpectedly dense"
    per_seg: dict[int, list] = {}
    for ev in events:
        for wid in index.lookup(ev.key):
            if ev.revision >= filters[wid][2]:
                per_seg.setdefault(wid, []).append(ev)
    per_dev = DeviceFanout().deliver(events, narrow, version=1)
    assert per_dev == per_seg, "block deliveries diverged from segment index"

    # ---- leg 2: block vs per-batch throughput ---------------------------
    from kubebrain_tpu.backend.backend import EVENT_BATCH

    legacy = FanoutMatcher()

    def run_block():
        return matcher.deliver(events, specs, version=2)

    def run_per_batch():
        # the pre-block hub pipeline: EVENT_BATCH-chunked legacy masks +
        # per-column demux (watcherhub.stream's legacy device branch)
        out: dict[int, list] = {}
        for i in range(0, n_events, EVENT_BATCH):
            chunk = events[i:i + EVENT_BATCH]
            mask = legacy(chunk, specs, version=2)
            for w in np.nonzero(mask.any(axis=0))[0]:
                wid = specs[int(w)][0]
                rows = np.nonzero(mask[:, w])[0]
                out.setdefault(wid, []).extend(chunk[int(e)] for e in rows)
        return out

    block_delivery = run_block()  # warm (pays jit compiles)
    per_batch_delivery = run_per_batch()
    assert block_delivery == per_batch_delivery, \
        "block deliveries diverged from per-batch path"
    deliveries = sum(len(v) for v in block_delivery.values())

    block_dt = min(_timeit(run_block) for _ in range(iters))
    per_batch_dt = min(_timeit(run_per_batch) for _ in range(iters))
    speedup = per_batch_dt / block_dt
    events_per_sec = n_events / block_dt

    # ---- leg 3: hub lag through the device block route ------------------
    metrics = PrometheusMetrics()
    hub_matcher = DeviceFanout()
    hub = WatcherHub(fanout_matcher=hub_matcher)
    hub.set_metrics(metrics)
    hub_matcher.set_metrics(metrics)
    for _wid, s, e, r in specs:
        hub.add_watcher(s, e, r)
    rev = 300 + n_events
    for _ in range(rounds):
        batch = _fanout_events(n_events, rev0=rev, rng=rng,
                               ts=_time.monotonic())
        hub.stream(batch)
        rev += n_events
    assert hub_matcher.stats["blocks"] == rounds, (
        "hub did not route stream() through the device block path",
        hub_matcher.stats)
    snap = slo.parse_prom(metrics.http_handler()()[1].decode())
    lag_p99 = slo.hist_quantile(snap, "kb_watch_lag_seconds", 0.99,
                                point="queue")
    hub.close()

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    report = {
        "schema": "kubebrain-fanout/v1",
        "platform": platform_info(),
        "watchers": n_watchers,
        "broad_watchers": n_broad,
        "events_per_block": n_events,
        "rounds": rounds,
        "deliveries_per_block": deliveries,
        "watch_fanout_events_per_sec": round(events_per_sec),
        "block_seconds": round(block_dt, 4),
        "per_batch_seconds": round(per_batch_dt, 4),
        "speedup_vs_per_batch": round(speedup, 3),
        "mask_identical_to_brute_oracle": True,
        "deliveries_identical_to_segment_index": True,
        "hub_routed_blocks": hub_matcher.stats["blocks"],
        "dispatches": matcher.stats["dispatches"],
        "redispatches": matcher.stats["redispatches"],
        "table": matcher.table.stats(),
        "lag_p99_s": lag_p99,
        "lag_bound_s": lag_bound,
        "acceptance_lag_p99": ("pass" if lag_p99 is not None
                               and lag_p99 <= lag_bound else "fail"),
        "acceptance_2x_cpu": "pass" if speedup >= 2.0 else "fail",
        "acceptance_2x_tpu": ("pass" if on_tpu and speedup >= 2.0
                              else "pending_tpu"),
    }
    out_path = os.environ.get("KB_FANOUT_OUT") or _next_fanout_path(
        os.path.dirname(os.path.abspath(__file__)))
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps({
        "metric": "watch fan-out events/sec at %dk watchers" % (n_watchers // 1000),
        "value": round(events_per_sec),
        "unit": "events/sec",
        "vs_baseline": round(speedup, 3),
        "platform": report["platform"],
        "detail": {k: v for k, v in report.items()
                   if k not in ("schema", "platform")},
    }))
    # asserted AFTER the report is emitted so a failing run still leaves
    # the timings on record (the nonzero exit fails CI either way)
    assert speedup >= 2.0, (
        f"block path {block_dt:.3f}s not >= 2x per-batch {per_batch_dt:.3f}s")
    assert lag_p99 is not None and lag_p99 <= lag_bound, (
        f"kb_watch_lag_seconds p99 {lag_p99} over bound {lag_bound}")


def _timeit(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _compact_dataset(n_keys: int, seed: int = 7):
    """Deterministic pre-compaction store content with a realistic victim
    mix (docs/compaction.md): superseded version chains, tombstoned chains
    (fully doomed incl. the rev record), TTL-expired ``/events/`` rows, and
    clean singleton survivors. Returns ``(rows, ttl_boundary_rev,
    compact_rev, n_version_rows)`` where ``rows`` is a list of
    ``(internal_key, value)`` pairs ready to batch-put into ANY engine —
    the oracle and device stores load byte-identical content."""
    import random as _random

    from kubebrain_tpu import coder
    from kubebrain_tpu.backend.common import TOMBSTONE

    rng = _random.Random(seed)
    rows: list[tuple[bytes, bytes]] = []
    rev = 0
    n_version_rows = 0
    # kube-realistic object payloads (pods serialize to KBs, not tens of
    # bytes): deterministic sizes in [256, 2048) sliced from one pattern
    # buffer — content doesn't matter to compaction, footprint does
    payload = bytes(range(256)) * 8

    def body(i):
        return payload[: rng.randrange(256, 2048)] + b"#%d" % i

    def version(uk, value):
        nonlocal rev, n_version_rows
        rev += 1
        n_version_rows += 1
        rows.append((coder.encode_object_key(uk, rev), value))
        return rev

    def rev_record(uk, latest, deleted):
        rows.append((coder.encode_revision_key(uk),
                     coder.encode_rev_value(latest, deleted=deleted)))

    # phase 1: expired /events/ rows — everything at or below this boundary
    # revision is TTL-expired (the seeded compact history ages it past the
    # EVENTS_TTL cutoff)
    n_events = n_keys // 4
    for i in range(n_events):
        uk = b"/events/ns%02d/ev-%06d" % (i % 20, i)
        r = version(uk, body(i))
        rev_record(uk, r, False)
    ttl_boundary_rev = rev

    # phase 2: registry churn — chains, tombstones, singletons
    for i in range(n_keys - n_events):
        ns = i % 32
        uk = b"/registry/pods/ns%02d/pod-%06d" % (ns, i)
        shape = i % 3
        if shape == 0:  # superseded chain: 2-4 doomed + 1 surviving version
            r = version(uk, body(i))
            for j in range(2 + rng.randrange(3)):
                r = version(uk, body(i + j))
            rev_record(uk, r, False)
        elif shape == 1:  # tombstoned: the whole chain compacts away
            version(uk, body(i))
            r = version(uk, TOMBSTONE)
            rev_record(uk, r, True)
        else:  # clean singleton survivor
            r = version(uk, body(i))
            rev_record(uk, r, False)
    # load in sorted key order: engines keeping a sorted key index (memkv's
    # insort, LSM memtables) then pay O(1) tail appends instead of O(n)
    # mid-list inserts — bulk loads are sorted in any real migration, and
    # both engines load the identical sequence either way
    rows.sort(key=lambda kv: kv[0])
    return rows, ttl_boundary_rev, rev, n_version_rows


def _load_store(store, rows, batch: int = 1024) -> None:
    for b0 in range(0, len(rows), batch):
        bw = store.begin_batch_write()
        for k, v in rows[b0 : b0 + batch]:
            bw.put(k, v)
        bw.commit()


def _dump_store(store) -> list:
    from kubebrain_tpu import coder

    lo, hi = coder.internal_range(b"", b"")
    return list(store.iter(lo, hi))


def bench_compact() -> None:
    """Engine-level compaction bench (make bench-compact; docs/compaction.md):
    three compactors over byte-identical store content with a realistic
    victim mix —

    - **device**: the stored-domain pipeline (victim kernel → shard-local
      index pull → victim-only decode GC → survivor gather + k-way merge,
      dirty shards only);
    - **host path**: the CURRENT-until-this-PR mirror half — identical
      marking + GC, but the mirror absorbs the compaction through the
      decode-everything → re-dictionary → re-partition full rebuild
      (`compact_force_full`, preserved as the fallback rung);
    - **oracle**: the engine-generic sequential compactor
      (backend/scanner.py) — the semantic ground truth.

    Gates: post-compact store state byte-identical across ALL three,
    serving results identical, ZERO full rebuilds / re-dictionary encodes
    on the device path, and (at the >= 1M-row acceptance size, on the
    native engine) compact_rows_per_sec >= 2x the host path on CPU-sim —
    the TPU bar is the same 2x asserted on-TPU and stamped pending_tpu
    off it. The inner engine is the NATIVE store when its library loads
    (KB_COMPACT_ENGINE=auto|native|memkv): that is the production
    configuration — compaction GC rides the C `bulk_gc`/`prune` fast
    paths in all three compactors, so the measured difference is the
    mirror half this PR moved into the stored domain, not Python store
    mutation (the memkv fallback still runs every identity gate, plus the
    TTL-expiry class the native engine handles natively). One untimed
    warm-up pass pays every jit compile before either timed pass (the
    shapes are identical — same dataset). Report: COMPACT_rNN.json
    (kubebrain-compact/v1) via KB_COMPACT_OUT."""
    import time as _time

    import jax

    from kubebrain_tpu import coder
    from kubebrain_tpu.backend.scanner import CompactHistory, Scanner
    from kubebrain_tpu.storage import new_storage

    # default sizes the acceptance shape: ~2 version rows per key on
    # average, so 520k keys ≈ 1.04M version rows (>= the 1M-row bar)
    n_keys = int(os.environ.get("KB_BENCH_KEYS", 520_000))
    seed = int(os.environ.get("KB_BENCH_SEED", 7))
    engine = os.environ.get("KB_COMPACT_ENGINE", "auto")
    if engine == "auto":
        try:
            probe = new_storage("native")
            probe.close()
            engine = "native"
        except Exception:
            engine = "memkv"
    inner_kw = {} if engine == "native" else {"ttl_supported": False}
    rows, ttl_rev, compact_rev, n_version_rows = _compact_dataset(n_keys, seed)
    lo, hi = coder.internal_range(b"", b"")
    aged = _time.time() - 7200  # compact-history entry older than EVENTS_TTL

    def tpu_scanner():
        store = new_storage("tpu", inner=engine, **inner_kw)
        _load_store(store, rows)
        hist = CompactHistory()
        hist.log(ttl_rev, now=aged)
        sc = store.make_scanner(
            get_compact_revision=lambda *_a: 0, compact_history=hist)
        sc.publish()  # mirror build off the clock (boot cost, not compact)
        return store, sc

    def run_tpu_path(force_full):
        store, sc = tpu_scanner()
        sc.compact_force_full = force_full
        enc_before = sc._mirror.encoding
        t0 = _time.time()
        stats = sc.compact(lo, hi, compact_rev)
        return store, sc, stats, _time.time() - t0, enc_before

    # ---- warm-up: pays every jit compile off BOTH clocks (the legacy
    # path shares the marking kernels; its full rebuild is numpy-only)
    w_store, w_sc, _w_stats, _, _ = run_tpu_path(False)
    w_sc.close()
    w_store.close()

    # ---- device path: the stored-domain pipeline ------------------------
    dev_store, dev_sc, dev_stats, dev_dt, encoding_before = run_tpu_path(False)
    dev_rate = n_version_rows / dev_dt

    # ---- host path: identical marking + GC, legacy mirror rebuild -------
    leg_store, leg_sc, leg_stats, leg_dt, _enc = run_tpu_path(True)
    leg_rate = n_version_rows / leg_dt
    assert leg_stats.mirror_path == "full_rebuild", leg_stats.mirror_path

    # ---- oracle: the engine-generic sequential compactor ----------------
    orc_store = new_storage(engine, **inner_kw)
    _load_store(orc_store, rows)
    hist = CompactHistory()
    hist.log(ttl_rev, now=aged)
    orc_sc = Scanner(orc_store, lambda *_a: 0, compact_history=hist)
    t0 = _time.time()
    orc_stats = orc_sc.compact(lo, hi, compact_rev)
    orc_dt = _time.time() - t0
    orc_rate = n_version_rows / orc_dt

    # ---- gates ----------------------------------------------------------
    # 1. post-compact store state byte-identical across all three
    orc_dump = _dump_store(orc_store)
    dev_dump = _dump_store(dev_store._inner)
    leg_dump = _dump_store(leg_store._inner)
    assert orc_dump == dev_dump, (
        f"device store diverged from oracle: {len(orc_dump)} vs "
        f"{len(dev_dump)} rows")
    assert orc_dump == leg_dump, "legacy store diverged from oracle"
    # 2. serving results identical (mirrors vs oracle host scan)
    orc_kvs = [(kv.key, kv.value, kv.revision)
               for kv in orc_sc.range_(b"", b"", compact_rev)[0]]
    for sc in (dev_sc, leg_sc):
        got = [(kv.key, kv.value, kv.revision)
               for kv in sc.range_(b"", b"", compact_rev)[0]]
        assert got == orc_kvs, "post-compact serving results diverged"
    # 3. steady state: no full rebuild, no re-dictionary, stored path
    assert dev_sc.full_rebuild_total == 0, \
        f"device compact took {dev_sc.full_rebuild_total} full rebuild(s)"
    assert dev_sc._mirror.encoding is encoding_before, \
        "device compact re-dictionaried the mirror"
    assert dev_stats.mirror_path == "stored_incremental", dev_stats.mirror_path
    # 4. victim classification equal to the oracle's
    for f in ("deleted_versions", "deleted_tombstones", "deleted_rev_records",
              "expired_ttl"):
        assert getattr(dev_stats, f) == getattr(orc_stats, f), (
            f, getattr(dev_stats, f), getattr(orc_stats, f))
        assert getattr(leg_stats, f) == getattr(orc_stats, f), f

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    speedup = dev_rate / leg_rate
    # the CPU-sim acceptance bar holds at the >= 1M-row size on the
    # production (native) engine — small smoke runs are identity gates
    # only (fixed dispatch cost dominates them), and the memkv fallback
    # measures Python store mutation, not the mirror pipeline; the TPU
    # bar is the same 2x, asserted on-TPU, pending_tpu off it
    at_acceptance_size = n_version_rows >= 1_000_000 and engine == "native"
    acceptance_cpu = ("pass" if speedup >= 2.0 and engine == "native" else
                      ("fail" if at_acceptance_size else
                       ("memkv_fallback" if engine != "native" else "small_n")))

    report = {
        "schema": "kubebrain-compact/v1",
        "platform": platform_info(),
        "keys": n_keys,
        "rows": n_version_rows,
        "compact_rows_per_sec": round(dev_rate),
        "host_rows_per_sec": round(leg_rate),
        "oracle_rows_per_sec": round(orc_rate),
        "speedup_vs_host": round(speedup, 3),
        "compact_seconds": round(dev_dt, 3),
        "host_seconds": round(leg_dt, 3),
        "oracle_seconds": round(orc_dt, 3),
        "victims": {
            "superseded": dev_stats.deleted_versions,
            "tombstone": dev_stats.deleted_tombstones,
            "ttl_expired": dev_stats.expired_ttl,
            "rev_record": dev_stats.deleted_rev_records,
        },
        "survivor_rows": dev_stats.survivor_rows,
        "dirty_partitions": dev_stats.dirty_partitions,
        "mirror_path": dev_stats.mirror_path,
        "phase_seconds": {k: round(v, 4)
                          for k, v in dev_stats.phase_seconds.items()},
        "host_phase_seconds": {k: round(v, 4)
                               for k, v in leg_stats.phase_seconds.items()},
        "byte_identical_store": True,
        "byte_identical_serving": True,
        "full_rebuild_total": dev_sc.full_rebuild_total,
        "re_dictionary": dev_sc._mirror.encoding is not encoding_before,
        "kernel": dev_sc._scan_kernel,
        "engine": engine,
        # one untimed warm-up pass paid every jit compile before either
        # timed pass (identical shapes — same dataset)
        "warmed": True,
        "acceptance_2x_cpu": acceptance_cpu,
        "acceptance_2x_tpu": ("pass" if on_tpu and speedup >= 2.0
                              else "pending_tpu"),
    }
    out_path = os.environ.get("KB_COMPACT_OUT")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
    print(json.dumps({
        "metric": "compaction rows/sec",
        "value": round(dev_rate),
        "unit": "rows/sec",
        "vs_baseline": round(speedup, 3),
        "platform": report["platform"],
        "detail": {k: v for k, v in report.items()
                   if k not in ("schema", "platform")},
    }))

    for sc in (dev_sc, leg_sc, orc_sc):
        sc.close()
    for st in (dev_store, leg_store, orc_store):
        st.close()
    # asserted AFTER the report is emitted so a failing run still leaves
    # the phase breakdown on record (the nonzero exit fails CI either way)
    if at_acceptance_size:
        assert speedup >= 2.0, (
            f"device compact {dev_rate:.0f} rows/s < 2x host path "
            f"{leg_rate:.0f} rows/s at acceptance size")


def bench_insert() -> None:
    """Reference headline: insert throughput + insert→event delivery latency
    through the full MVCC write path (BASELINE.md: KubeBrain/TiKV 28.6k
    ops/s, event latency avg 11.9-13.5ms p99 23-41ms) over the C++ engine."""
    import queue as _q
    import threading

    from kubebrain_tpu.backend import Backend, BackendConfig
    from kubebrain_tpu.storage import new_storage

    n_ops = int(os.environ.get("KB_BENCH_OPS", 20_000))
    n_threads = int(os.environ.get("KB_BENCH_THREADS", 8))
    store = new_storage("native")
    backend = Backend(store, BackendConfig(event_ring_capacity=200_000))
    value = b"x" * 512  # reference workload: 512B values
    per = n_ops // n_threads

    # a watcher measuring write→event delivery latency (reference's "insert
    # event" rows): writers stamp send time in the value
    _, wq = backend.watch(b"/registry/pods/")
    ev_lat: list[float] = []
    stop_watch = threading.Event()

    def watcher():
        while not stop_watch.is_set():
            try:
                batch = wq.get(timeout=0.2)
            except _q.Empty:
                continue
            if batch is None:
                return
            now = time.time()
            for ev in batch:
                sent = float(ev.value[:20])
                ev_lat.append(now - sent)

    wt = threading.Thread(target=watcher, daemon=True)
    wt.start()

    def writer(w):
        for i in range(per):
            stamped = (b"%020.6f" % time.time()) + value
            backend.create(b"/registry/pods/bench-%02d-%06d" % (w, i), stamped)

    threads = [threading.Thread(target=writer, args=(w,)) for w in range(n_threads)]
    t0 = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.time() - t0
    rate = per * n_threads / dt
    time.sleep(0.5)
    stop_watch.set()
    backend.close()
    store.close()
    lat_sorted = sorted(ev_lat) or [0.0]
    print(json.dumps({
        "metric": "insert ops/sec",
        "value": round(rate),
        "unit": "ops/sec",
        "vs_baseline": round(rate / 28_644, 3),  # reference KubeBrain/TiKV insert
        "platform": platform_info(),
        "detail": {
            "ops": per * n_threads, "threads": n_threads,
            "value_bytes": 512, "engine": "native(C++)",
            "events_delivered": len(ev_lat),
            "event_latency_avg_ms": round(sum(lat_sorted) / len(lat_sorted) * 1e3, 2),
            "event_latency_p99_ms": round(lat_sorted[int(len(lat_sorted) * 0.99) - 1] * 1e3, 2),
            "reference_event_latency": "avg 11.9-13.5ms p99 23-41ms",
        },
    }))


def bench_delete() -> None:
    """The reference's documented weakness: delete throughput (published
    4,847-5,028 ops/s vs etcd's 10.8k; read-before-delete + CAS,
    benchmark.md:56-61). Here the whole sequence is one native call."""
    import threading

    from kubebrain_tpu.backend import Backend, BackendConfig
    from kubebrain_tpu.storage import new_storage

    n_ops = int(os.environ.get("KB_BENCH_OPS", 20_000))
    n_threads = int(os.environ.get("KB_BENCH_THREADS", 8))
    store = new_storage("native")
    backend = Backend(store, BackendConfig(event_ring_capacity=300_000))
    value = b"x" * 512
    per = n_ops // n_threads
    for w in range(n_threads):
        for i in range(per):
            backend.create(b"/registry/pods/del-%02d-%06d" % (w, i), value)

    def deleter(w):
        for i in range(per):
            backend.delete(b"/registry/pods/del-%02d-%06d" % (w, i))

    threads = [threading.Thread(target=deleter, args=(w,)) for w in range(n_threads)]
    t0 = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.time() - t0
    rate = per * n_threads / dt
    backend.close()
    store.close()
    print(json.dumps({
        "metric": "delete ops/sec",
        "value": round(rate),
        "unit": "ops/sec",
        "vs_baseline": round(rate / 5_028, 3),  # reference's published delete
        "platform": platform_info(),
        "detail": {"ops": per * n_threads, "threads": n_threads,
                   "engine": "native(C++)", "reference": "4.8-5.0k (KubeBrain), 10.8-11.2k (etcd)"},
    }))


def bench_grpc_list() -> None:
    """BASELINE config 1: etcd3 Range over 10k /registry/pods/* keys through
    the live gRPC surface. Measured through BOTH listeners of one server —
    the native frontend (kbfront, the production path) and the sync Python
    endpoint (round-2's recorded 208ms-p50 path) — so the ratio is the
    native front's win on the read path (VERDICT r2 next #6; reference read
    bar avg 7.9-11.9ms, docs/data/benchmark_rw.csv)."""
    import socket
    import subprocess

    from kubebrain_tpu.client import EtcdCompatClient

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    n_keys = int(os.environ.get("KB_BENCH_KEYS", 10_000))
    iters = int(os.environ.get("KB_BENCH_ITERS", 10))
    repo = os.path.dirname(os.path.abspath(__file__))
    py_port, front_port = free_port(), free_port()
    have_front = os.path.exists(os.path.join(repo, "native", "front", "kbfront"))
    args = [sys.executable, "-m", "kubebrain_tpu.cli", "--single-node",
            "--storage", "native", "--host", "127.0.0.1",
            "--client-port", str(py_port),
            "--peer-port", str(free_port()), "--info-port", str(free_port())]
    if have_front:
        args += ["--front-port", str(front_port)]
    server = subprocess.Popen(args, cwd=repo, stderr=subprocess.DEVNULL)
    c = EtcdCompatClient(f"127.0.0.1:{py_port}")
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            c.count(b"/x", b"/y")
            break
        except Exception:
            time.sleep(0.2)
    value = b"x" * 512
    for i in range(n_keys):
        c.create(b"/registry/pods/default/pod-%06d" % i, value)

    def measure(client):
        lat = []
        for _ in range(iters):
            t0 = time.time()
            kvs, _ = client.list(b"/registry/pods/", b"/registry/pods0", page=1000)
            lat.append(time.time() - t0)
            assert len(kvs) == n_keys
        return sorted(lat)[len(lat) // 2]

    py_p50 = measure(c)
    c.close()
    if have_front:
        cf = EtcdCompatClient(f"127.0.0.1:{front_port}")
        front_p50 = measure(cf)
        cf.close()
    else:
        front_p50 = py_p50
    server.terminate()
    server.wait(timeout=10)
    p50 = front_p50
    rate = n_keys / p50
    print(json.dumps({
        "metric": "grpc list keys/sec",
        "value": round(rate),
        "unit": "keys/sec",
        "vs_baseline": round(py_p50 / front_p50, 3),
        "platform": platform_info(),
        "detail": {"keys": n_keys, "list_p50_ms": round(p50 * 1e3, 2),
                   "py_endpoint_p50_ms": round(py_p50 * 1e3, 2),
                   "value_bytes": 512, "paged": 1000,
                   "transport": "etcd3 gRPC (kbfront)" if have_front
                                else "etcd3 gRPC (sync py)",
                   "baseline": "same list through the sync python endpoint"},
    }))


def bench_grpc_insert() -> None:
    """Over-the-wire insert throughput against the native frontend
    (kbfront), driven by the native load generator — the reference's
    methodology (an external Go benchmark tool, 300 concurrent etcd
    clients, 512B values, docs/benchmark.md:34-37). A Python grpcio load
    generator saturates a 2-vCPU box at ~2k ops/s of CLIENT-side
    interpreter cost; kbloadgen plays the Go tool's role at native speed
    so the measurement exercises the server, not the client.

    KB_BENCH_PYCLIENT=1 falls back to the round-1 methodology (32 Python
    grpcio client threads against the sync endpoint) for comparison.
    """
    import socket
    import threading

    from kubebrain_tpu.client import EtcdCompatClient

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    n_ops = int(os.environ.get("KB_BENCH_OPS", 50_000))
    use_pyclient = bool(os.environ.get("KB_BENCH_PYCLIENT"))
    repo = os.path.dirname(os.path.abspath(__file__))
    loadgen = os.path.join(repo, "native", "front", "kbloadgen")
    front_bin = os.path.join(repo, "native", "front", "kbfront")
    if not use_pyclient and not (os.path.exists(loadgen) and os.path.exists(front_bin)):
        use_pyclient = True

    port = free_port()
    args = [sys.executable, "-m", "kubebrain_tpu.cli", "--single-node",
            "--storage", "native", "--host", "127.0.0.1",
            "--client-port", str(free_port() if not use_pyclient else port),
            "--peer-port", str(free_port()), "--info-port", str(free_port())]
    if not use_pyclient:
        args += ["--front-port", str(port)]
    use_tls = bool(os.environ.get("KB_BENCH_TLS")) and not use_pyclient
    tls_dir = None
    if use_tls:
        import tempfile

        from kubebrain_tpu.util.selfsigned import gen_self_signed

        tls_dir = tempfile.mkdtemp(prefix="kb-bench-tls-")
        cert_file, key_file = gen_self_signed(tls_dir, "kb-bench", (), ("127.0.0.1",))
        args += ["--cert-file", cert_file, "--key-file", key_file]
    server = subprocess.Popen(args, cwd=repo, stderr=subprocess.DEVNULL)
    value = b"x" * 512
    probe = EtcdCompatClient(f"127.0.0.1:{port}")
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            probe.count(b"/x", b"/y")
            break
        except Exception:
            time.sleep(0.2)
    probe.close()

    try:
        if use_pyclient:
            n_clients = int(os.environ.get("KB_BENCH_CLIENTS", 32))
            n_ops = int(os.environ.get("KB_BENCH_OPS", 10_000))
            per = n_ops // n_clients

            def client_writer(w):
                c = EtcdCompatClient(f"127.0.0.1:{port}")
                for i in range(per):
                    c.create(b"/registry/pods/g-%03d-%06d" % (w, i), value)
                c.close()

            threads = [threading.Thread(target=client_writer, args=(w,))
                       for w in range(n_clients)]
            t0 = time.time()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.time() - t0
            rate = per * n_clients / dt
            detail = {"ops": per * n_clients, "clients": n_clients,
                      "value_bytes": 512, "transport": "etcd3 gRPC (sync, py client)"}
        else:
            n_conns = int(os.environ.get("KB_BENCH_CLIENTS", 8))
            inflight = int(os.environ.get("KB_BENCH_INFLIGHT", 16))
            lg_args = [loadgen, "127.0.0.1", str(port), str(n_ops),
                       str(n_conns), str(inflight), "512"]
            if use_tls:
                lg_args.append("--tls")
            out = subprocess.run(
                lg_args, capture_output=True, text=True, timeout=300,
            )
            if out.returncode != 0 or not out.stdout.strip():
                raise RuntimeError(
                    f"kbloadgen failed rc={out.returncode}: {out.stderr[-500:]}")
            res = json.loads(out.stdout.strip().splitlines()[-1])
            assert res["failed"] == 0, res
            rate = res["rate"]
            detail = {"ops": res["ops"], "conns": n_conns, "inflight": inflight,
                      "value_bytes": 512,
                      "transport": "etcd3 gRPC (kbfront%s)" % (
                          " TLS" if use_tls else ""),
                      "avg_ms": round(res["avg_us"] / 1e3, 2),
                      "p50_ms": round(res["p50_us"] / 1e3, 2),
                      "p99_ms": round(res["p99_us"] / 1e3, 2)}
    finally:
        server.terminate()
        server.wait(timeout=10)
        if tls_dir is not None:
            import shutil

            shutil.rmtree(tls_dir, ignore_errors=True)  # unencrypted key
    print(json.dumps({
        "metric": "grpc insert ops/sec",
        "value": round(rate),
        "unit": "ops/sec",
        "vs_baseline": round(rate / 28_644, 3),
        "platform": platform_info(),
        "detail": detail,
    }))


def bench_rebuild() -> None:
    """TPU-mirror rebuild over the remote tier (the composed production
    topology, --storage=tpu --inner-storage=remote): bulk OP_EXPORT vs the
    per-row iter+decode path, both over a real kbstored subprocess.
    Reference analogue: the TiKV adapter feeding the scanner's partition
    map (tikv.go:38-153). KB_BENCH_KEYS keys x 2 revisions."""
    import socket

    from kubebrain_tpu import coder
    from kubebrain_tpu.parallel.mesh import make_mesh
    from kubebrain_tpu.storage import new_storage
    from kubebrain_tpu.storage.remote import RemoteKvStorage

    n_keys = int(os.environ.get("KB_BENCH_KEYS", 100_000))
    rows = n_keys * 2

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    stored = subprocess.Popen(
        [os.path.join(os.path.dirname(__file__), "native", "kvrpc", "kbstored"),
         str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
    )
    try:
        assert b"READY" in stored.stdout.readline(), "kbstored failed to start"

        remote = new_storage("remote", address=f"127.0.0.1:{port}", pool=4)
        t0 = time.time()
        rev = 0
        for base in range(0, n_keys, 2000):
            b = remote.begin_batch_write()
            for i in range(base, min(base + 2000, n_keys)):
                k = b"/registry/pods/p%07d" % i
                for _ in range(2):
                    rev += 1
                    b.put(coder.encode_object_key(k, rev), b"v" * 64)
            b.commit()
        print(f"[bench] loaded {rows} rows into kbstored in {time.time()-t0:.1f}s",
              file=sys.stderr)

        store = new_storage("tpu", inner="remote", mesh=make_mesh(),
                            address=f"127.0.0.1:{port}", pool=4)
        scanner = store.make_scanner(get_compact_revision=lambda: 0)

        def timed_rebuild():
            scanner.mark_uncertain()
            t = time.time()
            scanner.publish()
            return time.time() - t

        fast = min(timed_rebuild() for _ in range(3))

        # hide the bulk export: the rebuild falls to per-row iter + decode
        orig = RemoteKvStorage.export_mvcc
        del RemoteKvStorage.export_mvcc
        try:
            slow = timed_rebuild()
        finally:
            RemoteKvStorage.export_mvcc = orig

        rate = rows / fast
        print(f"[bench] rebuild fast {fast*1e3:.0f}ms slow {slow*1e3:.0f}ms "
              f"({slow/fast:.1f}x)", file=sys.stderr)
        print(json.dumps({
            "metric": "mirror-rebuild rows/sec (over kbstored)",
            "value": int(rate),
            "unit": "rows/sec",
            "vs_baseline": round(slow / fast, 3),
            "platform": platform_info(),
            "detail": {
                "rows": rows,
                "bulk_export_ms": round(fast * 1e3, 1),
                "per_row_ms": round(slow * 1e3, 1),
                "baseline": "per-row iter+decode rebuild over the same wire",
            },
        }))
        store.close()
    finally:
        stored.terminate()
        stored.wait(timeout=5)


def bench_sim() -> None:
    """BASELINE config 5: kube-apiserver informer simulation OVER THE WIRE —
    N long-lived etcd Watch streams (default 10k) through the native
    frontend (kbfront), then a create load into the watched namespaces;
    watcher-side event-delivery latency measured end to end by the native
    load generator. Reference bar: insert event latency avg 11.9-13.5ms,
    p99 23-41ms on 3x12 cores (docs/data/benchmark_insert.csv).

    KB_BENCH_INPROC=1 falls back to the round-1 in-process variant."""
    if not os.environ.get("KB_BENCH_INPROC"):
        return _bench_sim_wire()
    import threading

    from kubebrain_tpu.backend import Backend, BackendConfig
    from kubebrain_tpu.ops.fanout import FanoutMatcher
    from kubebrain_tpu.storage import new_storage

    n_watchers = int(os.environ.get("KB_BENCH_WATCHERS", 1_000))
    n_ops = int(os.environ.get("KB_BENCH_OPS", 10_000))
    n_threads = int(os.environ.get("KB_BENCH_THREADS", 4))
    n_ns = 50

    store = new_storage("native")
    backend = Backend(store, BackendConfig(
        event_ring_capacity=max(200_000, n_ops * 2),
        fanout_matcher=FanoutMatcher(),
    ))
    watch_queues = []
    for i in range(n_watchers):
        _, q = backend.watch(b"/registry/pods/ns-%03d/" % (i % n_ns))
        watch_queues.append(q)

    delivered = [0]
    stop = False

    def drain():
        while not stop:
            for q in watch_queues:
                try:
                    while True:
                        batch = q.get_nowait()
                        if batch:
                            delivered[0] += len(batch)
                except Exception:
                    pass
            time.sleep(0.01)

    drainer = threading.Thread(target=drain, daemon=True)
    drainer.start()

    per = n_ops // n_threads
    value = b"x" * 512

    def writer(w):
        for i in range(per):
            key = b"/registry/pods/ns-%03d/pod-%02d-%06d" % (i % n_ns, w, i)
            rev = backend.create(key, value)
            if i % 10 == 0:
                backend.list_(b"/registry/pods/ns-%03d/" % (i % n_ns),
                              b"/registry/pods/ns-%03d0" % (i % n_ns), limit=100)

    threads = [threading.Thread(target=writer, args=(w,)) for w in range(n_threads)]
    t0 = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.time() - t0
    time.sleep(0.5)
    stop = True
    rate = per * n_threads / dt
    backend.close()
    store.close()
    print(json.dumps({
        "metric": "apiserver-sim write ops/sec",
        "value": round(rate),
        "unit": "ops/sec",
        "vs_baseline": round(rate / 14_801, 3),  # reference mixed-RW insert low bound
        "platform": platform_info(),
        "detail": {
            "watchers": n_watchers, "ops": per * n_threads,
            "events_delivered": delivered[0],
            "lists_interleaved": per * n_threads // 10,
            "threads": n_threads, "engine": "native(C++)",
        },
    }))


def _bench_sim_wire() -> None:
    import socket

    from kubebrain_tpu.client import EtcdCompatClient

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    n_watchers = int(os.environ.get("KB_BENCH_WATCHERS", 10_000))
    n_ns = int(os.environ.get("KB_BENCH_NS", 500))
    n_ops = int(os.environ.get("KB_BENCH_OPS", 10_000))
    # throughput saturates by ~16 in-flight; deeper pipelines only add
    # queueing delay to the reported event latency
    n_conns = int(os.environ.get("KB_BENCH_CLIENTS", 4))
    inflight = int(os.environ.get("KB_BENCH_INFLIGHT", 4))
    repo = os.path.dirname(os.path.abspath(__file__))
    loadgen = os.path.join(repo, "native", "front", "kbloadgen")
    front_bin = os.path.join(repo, "native", "front", "kbfront")
    if not (os.path.exists(loadgen) and os.path.exists(front_bin)):
        raise RuntimeError("build native first: make -C native")

    port = free_port()
    args = [sys.executable, "-m", "kubebrain_tpu.cli", "--single-node",
            "--storage", "native", "--host", "127.0.0.1",
            "--client-port", str(free_port()), "--peer-port", str(free_port()),
            "--info-port", str(free_port()), "--front-port", str(port),
            "--tpu-fanout", "--grpc-workers", "8"]
    server = subprocess.Popen(args, cwd=repo, stderr=subprocess.DEVNULL)
    try:
        probe = EtcdCompatClient(f"127.0.0.1:{port}")
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                probe.count(b"/x", b"/y")
                break
            except Exception:
                time.sleep(0.3)
        probe.close()
        out = subprocess.run(
            [loadgen, "127.0.0.1", str(port), str(n_ops), str(n_conns),
             str(inflight), "512", "--watchers", str(n_watchers),
             "--ns", str(n_ns)],
            capture_output=True, text=True, timeout=900,
        )
        if out.returncode != 0 or not out.stdout.strip():
            raise RuntimeError(
                f"kbloadgen failed rc={out.returncode}: {out.stderr[-500:]}")
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res["failed"] == 0, res
        assert res["deliveries"] == res["expected_deliveries"], res
    finally:
        server.terminate()
        server.wait(timeout=10)
    print(json.dumps({
        "metric": "apiserver-sim write ops/sec",
        "value": round(res["rate"]),
        "unit": "ops/sec",
        "vs_baseline": round(res["rate"] / 14_801, 3),
        "platform": platform_info(),
        "detail": {
            "watchers": n_watchers, "namespaces": n_ns, "ops": res["ops"],
            "events_delivered": res["deliveries"],
            "event_latency_avg_ms": res["ev_avg_ms"],
            "event_latency_p50_ms": res["ev_p50_ms"],
            "event_latency_p99_ms": res["ev_p99_ms"],
            "insert_p50_ms": round(res["p50_us"] / 1e3, 1),
            "conns": n_conns, "inflight": inflight,
            "transport": "etcd3 gRPC (kbfront), native watch streams",
            "reference_event_latency": "avg 11.9-13.5ms p99 23-41ms (3x12 cores)",
        },
    }))


def bench_sched() -> None:
    """Scheduler microbench (make bench-smoke): randomized Range workloads
    over a real backend, scheduled (concurrent, coalesced, depth-bounded)
    vs unscheduled sequential. On the CPU fallback the two paths must be
    byte-identical per request — the scheduler is a throughput/fairness
    layer, never a semantics layer. Small by default (KB_BENCH_KEYS=2000)
    so it runs as a smoke check anywhere."""
    import random
    import threading

    from kubebrain_tpu.backend import Backend, BackendConfig
    from kubebrain_tpu.sched import SchedConfig, ensure_scheduler
    from kubebrain_tpu.storage import new_storage

    n_keys = int(os.environ.get("KB_BENCH_KEYS", 2_000))
    n_req = int(os.environ.get("KB_BENCH_OPS", 200))
    depth = int(os.environ.get("KB_SCHED_DEPTH", 4))
    rng = random.Random(0)

    store = new_storage("memkv")
    backend = Backend(store, BackendConfig(event_ring_capacity=max(8192, n_keys * 2)))
    sched = ensure_scheduler(backend, SchedConfig(depth=depth))
    for i in range(n_keys):
        backend.create(b"/registry/pods/ns-%02d/pod-%06d" % (i % 20, i), b"x" * 64)
    rev = backend.current_revision()

    workloads = []
    for _ in range(n_req):
        ns = rng.randrange(20)
        workloads.append((
            b"/registry/pods/ns-%02d/" % ns, b"/registry/pods/ns-%02d0" % ns,
            rng.choice([0, rev]), rng.choice([0, 50]),
        ))

    def fingerprint(res):
        out = [b"%d|%d|%d" % (res.revision, res.count, int(res.more))]
        for kv in res.kvs:
            out.append(kv.key + b"\x00" + kv.value + b"\x00%d" % kv.revision)
        return b"\xff".join(out)

    # unscheduled sequential baseline
    t0 = time.time()
    expect = [fingerprint(backend.list_(*w)) for w in workloads]
    seq_dt = time.time() - t0

    # scheduled, concurrent (8 client threads sharing the queue)
    results: list = [None] * n_req
    idx = iter(range(n_req))
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                try:
                    i = next(idx)
                except StopIteration:
                    return
            results[i] = fingerprint(sched.list_(*workloads[i], client="w"))

    threads = [threading.Thread(target=worker) for _ in range(8)]
    t0 = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sched_dt = time.time() - t0

    mismatches = sum(1 for a, b in zip(results, expect) if a != b)
    assert mismatches == 0, f"{mismatches}/{n_req} scheduled results diverged"

    # deterministic batch-formation check (ISSUE 5): plug the single slot of
    # a fresh scheduler, queue 8 distinct ranges + counts, release — they
    # must ride ONE backend batch and match sequential results byte for byte
    from kubebrain_tpu.sched import Lane

    store2 = new_storage("memkv")
    backend2 = Backend(store2, BackendConfig(event_ring_capacity=8192))
    sched2 = ensure_scheduler(backend2, SchedConfig(depth=1, batch=8))
    for i in range(200):
        backend2.create(b"/registry/pods/ns-%02d/p-%04d" % (i % 8, i), b"x" * 32)
    release = threading.Event()
    sched2.submit_async(release.wait, Lane.SYSTEM)
    time.sleep(0.1)
    outs: dict = {}

    def one_batched(i):
        ns = i % 8
        a, b = b"/registry/pods/ns-%02d/" % ns, b"/registry/pods/ns-%02d0" % ns
        if i % 3 == 2:
            outs[i] = ("count", sched2.count(a, b, client="w"))
        else:
            outs[i] = ("list", fingerprint(sched2.list_(a, b, 0, 0, client="w")))
    bthreads = [threading.Thread(target=one_batched, args=(i,)) for i in range(8)]
    for t in bthreads:
        t.start()
    time.sleep(0.3)
    release.set()
    for t in bthreads:
        t.join(30.0)
    assert sched2.batched > 0, "plugged slot formed no batch"
    batched_mismatches = 0
    for i in range(8):
        ns = i % 8
        a, b = b"/registry/pods/ns-%02d/" % ns, b"/registry/pods/ns-%02d0" % ns
        if i % 3 == 2:
            want = ("count", backend2.count(a, b))
        else:
            want = ("list", fingerprint(backend2.list_(a, b, 0, 0)))
        batched_mismatches += outs[i] != want
    assert batched_mismatches == 0, f"{batched_mismatches}/8 batched diverged"
    backend2.close()
    store2.close()

    print(json.dumps({
        "metric": "scheduled range reqs/sec",
        "value": round(n_req / sched_dt),
        "unit": "requests/sec",
        "vs_baseline": round(seq_dt / sched_dt, 3),
        "platform": platform_info(),
        "detail": {
            "requests": n_req, "keys": n_keys, "depth": depth,
            "byte_identical": True,
            "coalesced": sched.coalesced,
            "batched_riders": sched2.batched,
            "batched_byte_identical": True,
            "shed": {l.name.lower(): c for l, c in sched.shed_counts.items()},
            "sequential_reqs_per_sec": round(n_req / seq_dt),
            "baseline": "unscheduled sequential backend.list_",
        },
    }))
    backend.close()
    store.close()


def bench_write() -> None:
    """Write-path group commit bench (KB_BENCH_METRIC=write; BENCH_r06):
    ``write_txns_per_sec`` serial vs grouped — the SAME mixed
    create/update/delete workload at 8-writer concurrency through the
    scheduler, once with group commit off (``write_batch=1``) and once on
    (``write_batch=8``). Disjoint per-writer keyspaces make the runs
    commute, so final (key, value) state must be identical; exact
    byte-identity INCLUDING revisions is asserted separately with a
    deterministic plugged-slot group vs a sequential oracle (the same
    construction proof tests/test_write_batch.py pins).

    The second half runs grouped writes over the TPU engine (CPU-sim jnp
    kernel) with a concurrent reader crossing the merge threshold, and
    asserts the steady state NEVER takes the full host rebuild:
    ``full_rebuild_total == 0`` and ``merge_rows_total`` accounts every
    delta row that left the overlay (merged + still-pending == committed
    version rows since the initial publish).

    Bars: grouped >= 1.5x serial is asserted ON CPU (the win is dispatch
    and commit-path amortization, not device time); the TPU-engine merge
    numbers carry a ``pending_tpu`` stamp off-TPU like the other phases."""
    import random
    import threading

    from kubebrain_tpu.backend import Backend, BackendConfig
    from kubebrain_tpu.sched import Lane, SchedConfig, ensure_scheduler
    from kubebrain_tpu.storage import new_storage

    writers = int(os.environ.get("KB_BENCH_WRITERS", 8))
    ops_per_writer = int(os.environ.get("KB_BENCH_OPS", 400))
    depth = int(os.environ.get("KB_SCHED_DEPTH", 1))
    wbatch = int(os.environ.get("KB_SCHED_WRITE_BATCH", 8))

    def writer_stream(w: int):
        """Deterministic mixed stream for writer ``w`` over its own keys:
        create -> update -> update -> delete -> recreate ... (4:2:1 mix)."""
        rng = random.Random(1000 + w)
        live: dict[bytes, int] = {}
        ops = []
        for step in range(ops_per_writer):
            k = b"/registry/pods/w-%02d/p-%03d" % (w, rng.randrange(40))
            if k not in live:
                ops.append(("create", k, b"c%04d" % step))
            elif rng.random() < 0.6:
                ops.append(("update", k, b"u%04d" % step))
            else:
                ops.append(("delete", k))
            # liveness tracking only; revisions resolve at run time
            if ops[-1][0] == "delete":
                live.pop(k)
            else:
                live[k] = 1
        return ops

    streams = [writer_stream(w) for w in range(writers)]

    def run(write_batch: int):
        store = new_storage("memkv")
        backend = Backend(store, BackendConfig(event_ring_capacity=65536))
        sched = ensure_scheduler(backend, SchedConfig(
            depth=depth, write_batch=write_batch))
        errs: list = []

        def w_run(w: int):
            try:
                live: dict[bytes, int] = {}
                for op in streams[w]:
                    if op[0] == "create":
                        live[op[1]] = sched.create(op[1], op[2],
                                                   client=f"w{w}")
                    elif op[0] == "update":
                        live[op[1]] = sched.update(op[1], op[2],
                                                   live[op[1]],
                                                   client=f"w{w}")
                    else:
                        sched.delete(op[1], live.pop(op[1]),
                                     client=f"w{w}")
            except BaseException as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=w_run, args=(w,))
                   for w in range(writers)]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.time() - t0
        assert not errs, errs[0]
        state = sorted(
            (kv.key, kv.value) for kv in
            backend.list_(b"/registry/", b"/registry0", 0, 0).kvs)
        riders = sched.write_batched
        backend.close()
        store.close()
        return dt, state, riders

    total_ops = writers * ops_per_writer
    # warm up both paths (allocator/thread pools), then interleave
    # serial/grouped rounds and take best-of-3 each: the 2-vCPU CI box's
    # load swings dwarf the effect under test
    run(1)
    run(wbatch)
    rounds = [(run(1), run(wbatch)) for _ in range(3)]
    serial_dt, serial_state, _ = min(
        (s for s, _ in rounds), key=lambda r: r[0])
    grouped_dt, grouped_state, riders = min(
        (g for _, g in rounds), key=lambda r: r[0])
    assert grouped_state == serial_state, \
        "grouped and serial runs must converge to the same (key,value) state"
    assert riders > 0, "no write group ever formed at 8-writer concurrency"
    serial_rate = total_ops / serial_dt
    grouped_rate = total_ops / grouped_dt
    speedup = grouped_rate / serial_rate
    assert speedup >= 1.5, (
        f"group commit {speedup:.2f}x serial is under the 1.5x bar "
        f"({grouped_rate:.0f} vs {serial_rate:.0f} txns/s)")

    # --- deterministic formation: byte-identity incl. revisions ----------
    store = new_storage("memkv")
    backend = Backend(store, BackendConfig(event_ring_capacity=8192))
    sched = ensure_scheduler(backend, SchedConfig(depth=1, write_batch=8))
    o_store = new_storage("memkv")
    oracle = Backend(o_store, BackendConfig(event_ring_capacity=8192))
    release = threading.Event()
    sched.submit_async(release.wait, Lane.SYSTEM)
    time.sleep(0.1)
    keys = [b"/registry/pods/det/p-%d" % i for i in range(8)]
    outs: dict = {}
    det_errs: list = []

    def det_create(i: int) -> None:
        try:
            outs[i] = sched.create(keys[i], b"v%d" % i, client=f"c{i}")
        except BaseException as e:  # pragma: no cover
            det_errs.append(e)

    gthreads = [threading.Thread(target=det_create, args=(i,))
                for i in range(8)]
    for t in gthreads:
        t.start()
    time.sleep(0.3)
    release.set()
    for t in gthreads:
        t.join(30)
    assert not det_errs, det_errs[0]
    assert sched.write_batched > 0, "plugged slot formed no write group"
    for i in range(8):
        oracle.create(keys[i], b"v%d" % i)
    det_got = sorted(
        (kv.key, kv.value) for kv in
        backend.list_(b"/registry/pods/det/", b"/registry/pods/det0", 0, 0).kvs)
    det_want = sorted(
        (kv.key, kv.value) for kv in
        oracle.list_(b"/registry/pods/det/", b"/registry/pods/det0", 0, 0).kvs)
    # the dealt revision block is contiguous like the oracle's sequence
    det_identical = det_got == det_want and \
        sorted(outs.values()) == list(range(min(outs.values()),
                                            min(outs.values()) + 8))
    assert det_identical, "deterministic group diverged from the oracle"
    backend.close()
    store.close()
    oracle.close()
    o_store.close()

    # --- TPU-engine steady state: incremental merge, no full rebuild -----
    import jax  # noqa: F401  (forces backend init for platform_info)

    t_store = new_storage("tpu", inner="memkv")
    t_backend = Backend(t_store, BackendConfig(event_ring_capacity=65536))
    t_sched = ensure_scheduler(t_backend, SchedConfig(
        depth=depth, write_batch=wbatch))
    sc = t_backend.scanner
    sc._merge_threshold = 256
    rng = random.Random(17)
    seeded: dict[bytes, int] = {}
    for w in range(writers):
        for i in range(0, 40, 2):
            k = b"/registry/pods/w-%02d/p-%03d" % (w, i)
            seeded[k] = t_backend.create(k, b"seed")
    sc.publish()
    base_rows = len(sc._delta)  # 0 after publish
    stop_reader = threading.Event()

    def reader():
        while not stop_reader.is_set():
            t_backend.count(b"/registry/pods/", b"/registry/pods0")
            time.sleep(0.005)

    rt = threading.Thread(target=reader)
    rt.start()
    errs2: list = []

    def t_writer(w: int):
        try:
            live = {k: r for k, r in seeded.items()
                    if k.startswith(b"/registry/pods/w-%02d/" % w)}
            lrng = random.Random(2000 + w)
            for step in range(ops_per_writer):
                k = b"/registry/pods/w-%02d/p-%03d" % (w, lrng.randrange(40))
                if k not in live:
                    live[k] = t_sched.create(k, b"c%04d" % step,
                                             client=f"w{w}")
                elif lrng.random() < 0.6:
                    live[k] = t_sched.update(k, b"u%04d" % step, live[k],
                                             client=f"w{w}")
                else:
                    t_sched.delete(k, live.pop(k), client=f"w{w}")
        except BaseException as e:  # pragma: no cover
            errs2.append(e)

    tthreads = [threading.Thread(target=t_writer, args=(w,))
                for w in range(writers)]
    t0 = time.time()
    for t in tthreads:
        t.start()
    for t in tthreads:
        t.join()
    tpu_dt = time.time() - t0
    stop_reader.set()
    rt.join(10)
    assert not errs2, errs2[0]
    # quiesce before sampling: publish() enters the merge path and blocks
    # on the merge lock, so any in-flight write-kicked background merge
    # finishes (and its counters land) before we read them; it also
    # sweeps the delta tail, so pending is 0 and the accounting is exact
    sc.publish()
    merged = sc.merge_rows_total
    pending = len(sc._delta)
    full_rebuilds = sc.full_rebuild_total
    assert sc.merge_bg_errors == 0, sc._merge_bg_last_error
    assert full_rebuilds == 0, (
        f"steady-state churn took {full_rebuilds} full host rebuilds — "
        "the incremental merge must carry it")
    assert sc.merge_count > 0, "writes never crossed the merge threshold"
    assert merged + pending == total_ops - base_rows, (
        f"merge accounting leak: {merged} merged + {pending} pending != "
        f"{total_ops} committed rows")
    on_tpu = jax.devices()[0].platform == "tpu"
    t_backend.close()
    t_store.close()

    print(json.dumps({
        "metric": "write_txns_per_sec",
        "value": round(grouped_rate),
        "unit": "txns/sec",
        "vs_baseline": round(speedup, 3),
        "platform": platform_info(),
        "detail": {
            "writers": writers, "ops": total_ops, "depth": depth,
            "write_batch": wbatch,
            "serial_txns_per_sec": round(serial_rate),
            "grouped_txns_per_sec": round(grouped_rate),
            "grouped_riders": riders,
            "state_identical": True,
            "deterministic_group_byte_identical": det_identical,
            "grouped_acceptance_1_5x": "pass",  # asserted above, on CPU
            "mix": "create/update/delete ~40/36/24",
            "tpu_engine_merge": {
                "write_txns_per_sec": round((total_ops) / tpu_dt),
                "merges": sc.merge_count,
                "merge_rows_total": merged,
                "delta_rows_pending": pending,
                "full_rebuild_total": full_rebuilds,
                "accounting_exact": True,
                "merge_acceptance_tpu": "pass" if on_tpu else "pending_tpu",
            },
        },
    }))


def bench_cluster() -> None:
    """Cluster-scale workload replay (make bench-cluster N=...): the
    deterministic kube-apiserver traffic generator driven through the real
    gRPC front — pod churn + per-controller list/watch + node lease
    keepalives + compaction in ONE run — reporting per-lane p50/p99, shed
    rates, watch queue->wire lag, and lease counts reconciled against
    /metrics. Full report: WORKLOAD_rNN.json (docs/workloads.md).

    Env knobs: KB_BENCH_NODES (or N), KB_WORKLOAD_SEED, KB_WORKLOAD_DURATION
    (simulated seconds), KB_WORKLOAD_SCALE (sim seconds per real second),
    KB_WORKLOAD_STORAGE, KB_WORKLOAD_OUT (report path),
    KB_WORKLOAD_MESH_PART / KB_WORKLOAD_SCAN_PARTITIONS (sharded server,
    requires KB_WORKLOAD_STORAGE=tpu; docs/multichip.md),
    KB_WORKLOAD_COMPACT_S (compaction cadence in simulated seconds —
    the 5-min-compaction scenario; docs/compaction.md)."""
    from kubebrain_tpu.workload.runner import run_workload
    from kubebrain_tpu.workload.spec import WorkloadSpec

    nodes = int(os.environ.get("KB_BENCH_NODES", os.environ.get("N", 1000)))
    scenario = os.environ.get("KB_WORKLOAD_SCENARIO", "cluster")
    faults = os.environ.get("KB_WORKLOAD_FAULTS", "none")
    common = dict(
        seed=int(os.environ.get("KB_WORKLOAD_SEED", 0)),
        duration_s=float(os.environ.get("KB_WORKLOAD_DURATION", 30.0)),
        time_scale=float(os.environ.get("KB_WORKLOAD_SCALE", 5.0)),
        storage=os.environ.get("KB_WORKLOAD_STORAGE", "memkv"),
        mesh_part=int(os.environ.get("KB_WORKLOAD_MESH_PART", 0)),
        scan_partitions=int(os.environ.get("KB_WORKLOAD_SCAN_PARTITIONS", 0)),
        # read scale-out (docs/replication.md): spawn follower replicas;
        # the report then lands in REPLICA_rNN.json with a schema'd
        # `replica` section (make bench-cluster REPLICAS=2)
        replicas=int(os.environ.get("KB_WORKLOAD_REPLICAS", 0)),
    )
    # compaction-cadence knob (SIMULATED seconds; 0 = scenario default) —
    # `make bench-cluster COMPACT_S=300` drives the 5-min-compaction
    # scenario with serving-lane SLOs judged while compactions run
    compact_s = float(os.environ.get("KB_WORKLOAD_COMPACT_S", 0) or 0)
    if compact_s > 0:
        common["compact_interval_s"] = compact_s
    # watch fan-out offload (docs/watch.md): MESH_WAT=N shards the spawned
    # servers' watcher table over N (simulated) devices; the watch_heavy
    # scenario arms --tpu-fanout by itself, MESH_WAT works with any scenario
    mesh_wat = int(os.environ.get("KB_WORKLOAD_MESH_WAT", 0))
    if mesh_wat:
        common["tpu_fanout"] = True
        common["mesh_wat"] = mesh_wat
    if faults and faults != "none":
        # chaos mode (docs/faults.md): churn_heavy traffic under an armed
        # fault schedule; judged by the acknowledged-write consistency
        # check + per-kind injection reconcile; report -> CHAOS_rNN.json
        spec = WorkloadSpec.for_chaos(
            nodes, preset=faults,
            fault_seed=int(os.environ.get("KB_WORKLOAD_FAULT_SEED", 0)),
            **common)
    else:
        factory = {"cluster": WorkloadSpec.for_cluster,
                   "churn_heavy": WorkloadSpec.for_churn_heavy,
                   "churn-heavy": WorkloadSpec.for_churn_heavy,
                   "watch_heavy": WorkloadSpec.for_watch_heavy,
                   "watch-heavy": WorkloadSpec.for_watch_heavy}[scenario]
        spec = factory(nodes, **common)
    report = run_workload(spec, out_path=os.environ.get("KB_WORKLOAD_OUT") or None)
    lanes = {lane: {"p50_ms": s["p50_ms"], "p99_ms": s["p99_ms"],
                    "count": s["count"], "shed": s["shed"]}
             for lane, s in report["lanes"].items()}
    print(json.dumps({
        "metric": "cluster-replay ops/sec",
        "value": report["replay"]["ops_per_sec"],
        "unit": "ops/sec",
        "vs_baseline": 1.0 if report["slo"]["pass"] else 0.0,
        "platform": platform_info(),
        "detail": {
            "nodes": spec.nodes,
            "seed": spec.seed,
            "trace_sha256": report["trace"]["sha256"],
            "slo_pass": report["slo"]["pass"],
            "violations": report["slo"]["violations"],
            "lanes": lanes,
            "watchers": report["watch"]["watchers"],
            "watch_events": report["watch"]["events"],
            "watch_wire_lag_p99_s": report["watch"]["lag_wire_p99_s"],
            "keepalives_acked": report["leases"]["keepalives_acked"],
            "lease_expiries": report["leases"]["metrics"]["expired_delta"],
            "batched_requests": report["sched"]["batched_requests"],
            "reconcile_ok": report["reconcile"]["ok"],
            "replica": ({
                "replicas": spec.replicas,
                "rows_per_sec": report["replica"]["rows_per_sec"],
                "fence_probes": report["replica"]["fence_probes"],
                "endpoint_failovers": report["replica"]["endpoint_failovers"],
                "reconcile_ok": report["replica"]["reconcile"]["ok"],
            } if spec.replicas else None),
            "faults": ({
                "preset": spec.faults,
                "sha256": report["faults"]["schedule"]["sha256"],
                "injected": report["faults"]["injected"],
                "consistency_ok": report["faults"]["consistency"]["ok"],
                "degraded_p99_ms": report["faults"]["degraded"]["p99_ms"],
            } if report["faults"]["armed"] else {"preset": "none"}),
        },
    }))


#: timed serve passes per measurement point in multichip_phase — the
#: fastest pass is reported (least cross-process interference on shared
#: CPU boxes; on a quiet TPU host the passes agree within noise)
_SERVE_PASSES = 3


def _serve_best(serve_fn, sched):
    """Best-of-N timed serves: every pass must return identical results
    (asserted — a best-of measurement must not hide a divergence)."""
    best = None
    for _ in range(_SERVE_PASSES):
        results, rows, dt = serve_fn(sched)
        if best is not None:
            assert results == best[0], "serve passes diverged"
        if best is None or dt < best[2]:
            best = (results, rows, dt)
    return best


def multichip_phase(mesh_sizes, n_keys=20_000, n_req=64, depth=4, batch=8,
                    partitions=0, use_pallas=None, threads=8):
    """Serve the SAME scan workload through the request scheduler over the
    TPU engine at each mesh size and report the scaling curve — the
    promoted multichip path (the MULTICHIP dry runs never served a
    request). One host store is preloaded once; each mesh size wraps it in
    a fresh ``TpuKvStorage`` whose mirror shards over ``part`` across that
    many devices, then 8 distinct per-namespace Range/Count requests x
    ``n_req`` are pushed through the scheduler concurrently (composing
    with PR 2 lanes/pipelining and PR 5 query batching). Results are
    fingerprinted against the unscheduled sequential oracle AND across
    mesh sizes — byte identity is asserted, not sampled.

    Shared by ``bench_multichip`` (KB_BENCH_METRIC=multichip) and
    ``__graft_entry__.dryrun_multichip`` (the driver contract)."""
    import threading

    from kubebrain_tpu.backend import Backend, BackendConfig
    from kubebrain_tpu.parallel.mesh import make_mesh
    from kubebrain_tpu.sched import SchedConfig, ensure_scheduler
    from kubebrain_tpu.storage import new_storage
    from kubebrain_tpu.storage.tpu.engine import TRANSFER_METER, TpuKvStorage

    NS = 8
    inner = new_storage("memkv")
    loader = Backend(inner, BackendConfig(
        event_ring_capacity=max(8192, n_keys * 2)))
    for i in range(n_keys):
        loader.create(b"/registry/pods/ns-%02d/pod-%07d" % (i % NS, i),
                      b"x" * 64)
    loader.close()

    # request mix: per-namespace Range (3 of 4) and Count (1 of 4) — the
    # distinct-prefix shape that forms PR 5 query batches
    reqs = []
    for i in range(n_req):
        ns = i % NS
        bounds = (b"/registry/pods/ns-%02d/" % ns,
                  b"/registry/pods/ns-%02d0" % ns)
        reqs.append(("count" if i % 4 == 3 else "list", *bounds))

    def fingerprint(kind, res):
        if kind == "count":
            return b"count|%d|%d" % res
        out = [b"%d|%d|%d" % (res.revision, res.count, int(res.more))]
        for kv in res.kvs:
            out.append(kv.key + b"\x00" + kv.value + b"\x00%d" % kv.revision)
        return b"\xff".join(out)

    report = {
        "mesh_sizes": list(mesh_sizes),
        "rows_per_sec": {},
        "scaling_vs_1dev": {},
        "byte_identical": True,
        "batched_riders": {},
        "mirror_partitions": {},
        "host_transfer_bytes_per_req": {},
        "requests": n_req,
        "sched": {"depth": depth, "batch": batch, "threads": threads},
        "dataset": {"keys": n_keys, "namespaces": NS},
    }
    baseline_fps = None
    kernel = None

    def _serve(sched):
        results: list = [None] * n_req
        rows = [0] * n_req
        pending = iter(range(n_req))
        lock = threading.Lock()

        def worker():
            while True:
                with lock:
                    try:
                        i = next(pending)
                    except StopIteration:
                        return
                kind, s, e = reqs[i]
                if kind == "count":
                    res = sched.count(s, e, client=f"c{i % 4}")
                    rows[i] = res[0]
                else:
                    res = sched.list_(s, e, 0, 0, client=f"c{i % 4}")
                    rows[i] = len(res.kvs)
                results[i] = fingerprint(kind, res)

        pool = [threading.Thread(target=worker) for _ in range(threads)]
        t0 = time.monotonic()
        for t in pool:
            t.start()
        for t in pool:
            t.join()
        return results, rows, time.monotonic() - t0

    try:
        for ndev in mesh_sizes:
            mesh = make_mesh(n_devices=ndev)
            kw = {} if use_pallas is None else {"use_pallas": use_pallas}
            store = TpuKvStorage(inner, mesh=mesh, partitions=partitions, **kw)
            backend = Backend(store, BackendConfig(event_ring_capacity=8192))
            sched = ensure_scheduler(
                backend, SchedConfig(depth=depth, batch=batch))
            kernel = backend.scanner._scan_kernel
            # sequential unscheduled oracle; also publishes the mirror and
            # compiles this mesh size's kernels off the clock
            expect = []
            for kind, s, e in reqs:
                if kind == "count":
                    expect.append(fingerprint(kind, backend.count(s, e)))
                else:
                    expect.append(fingerprint(kind, backend.list_(s, e)))
            report["mirror_partitions"][str(ndev)] = \
                backend.scanner._mirror.partitions
            # mirror-compression capacity unlock (kubebrain-keyenc/v1):
            # identical at every mesh size — one dictionary, sharded rows
            report["key_encoding"] = {
                "schema": "kubebrain-keyenc/v1",
                **backend.scanner.encoding_stats()}
            report["mirror_bytes_per_row"] = \
                report["key_encoding"].get("mirror_bytes_per_row", 0.0)
            report["key_compression_ratio"] = \
                report["key_encoding"].get("key_compression_ratio", 1.0)

            # warm serve off the clock: the timed pass must not pay the
            # Q-gridded batch kernel's first compile (the sequential oracle
            # above never launches it — it only warms the single-query path)
            _serve(sched)
            batched0 = sched.batched  # cumulative — report the timed delta
            b0, _ = TRANSFER_METER.snapshot()
            results, rows, dt = _serve_best(_serve, sched)
            b1, _ = TRANSFER_METER.snapshot()

            mism = sum(1 for a, b in zip(results, expect) if a != b)
            assert mism == 0, (
                f"{mism}/{n_req} scheduled results diverged from the "
                f"sequential oracle at mesh={ndev}")
            if baseline_fps is None:
                baseline_fps = expect
            elif expect != baseline_fps:
                report["byte_identical"] = False
            report["rows_per_sec"][str(ndev)] = round(sum(rows) / dt)
            report["batched_riders"][str(ndev)] = round(
                (sched.batched - batched0) / _SERVE_PASSES)
            report["host_transfer_bytes_per_req"][str(ndev)] = round(
                (b1 - b0) / n_req / _SERVE_PASSES)
            backend.close()

        # RAW-mirror control at the smallest mesh: the prefix-encoded scan
        # must serve at equal-or-better p50 than the raw layout it
        # replaces (byte-identity asserted against the same oracle)
        if report["key_encoding"].get("encoded"):
            mesh = make_mesh(n_devices=mesh_sizes[0])
            kw = {} if use_pallas is None else {"use_pallas": use_pallas}
            store = TpuKvStorage(inner, mesh=mesh, partitions=partitions,
                                 encode_keys=False, **kw)
            backend = Backend(store, BackendConfig(event_ring_capacity=8192))
            sched = ensure_scheduler(
                backend, SchedConfig(depth=depth, batch=batch))
            for kind, s, e in reqs:  # publish + compile off the clock
                backend.count(s, e) if kind == "count" else backend.list_(s, e)
            _serve(sched)  # warm the batched path off the clock (as above)
            results, rows, dt = _serve_best(_serve, sched)
            assert results == baseline_fps, \
                "raw-control results diverged from the encoded mirror"
            report["rows_per_sec_raw_control"] = round(sum(rows) / dt)
            report["encoded_vs_raw"] = round(
                report["rows_per_sec"][str(mesh_sizes[0])]
                / max(1, report["rows_per_sec_raw_control"]), 3)
            backend.close()
    finally:
        inner.close()
    assert report["byte_identical"], "mesh sizes disagreed byte-for-byte"
    base = report["rows_per_sec"].get(str(mesh_sizes[0]), 0) or 1
    for k, v in report["rows_per_sec"].items():
        report["scaling_vs_1dev"][k] = round(v / base, 3)
    report["kernel"] = kernel
    return report


def bench_multichip() -> None:
    """Multichip sharded serving (the promoted MULTICHIP phase): the scan
    workload served through the scheduler at mesh sizes 1→8, byte-identical
    across sizes, reported as ``multichip_rows_per_sec`` plus a schema'd
    report (kubebrain-multichip/v1; KB_MULTICHIP_OUT=path writes it —
    MULTICHIP_rNN.json replaces the bare ``dryrun ok`` tail of r01–r05).

    Bars: on real TPU, near-linear scaling (>= 0.6x ideal at the largest
    mesh) is asserted; on CPU simulation the devices share the same
    sockets, so the bar is byte-identity plus no pathological slowdown
    (largest mesh >= 0.5x of 1-device) with the TPU bar recorded
    ``pending_tpu``."""
    import jax

    n_keys = int(os.environ.get("KB_BENCH_KEYS", 20_000))
    n_req = int(os.environ.get("KB_BENCH_OPS", 64))
    depth = int(os.environ.get("KB_SCHED_DEPTH", 4))
    batch = int(os.environ.get("KB_SCHED_BATCH", 8))
    partitions = int(os.environ.get("KB_SCAN_PARTITIONS", 0))
    n_dev = len(jax.devices())
    mesh_sizes = [k for k in (1, 2, 4, 8) if k <= n_dev]
    on_tpu = jax.devices()[0].platform == "tpu"

    phase = multichip_phase(
        mesh_sizes, n_keys=n_keys, n_req=n_req, depth=depth, batch=batch,
        partitions=partitions)
    top = str(mesh_sizes[-1])
    rate = phase["rows_per_sec"][top]
    base = phase["rows_per_sec"][str(mesh_sizes[0])]
    scaling = phase["scaling_vs_1dev"][top]
    if on_tpu:
        assert scaling >= 0.6 * mesh_sizes[-1], (
            f"multichip scaling {scaling:.2f}x at {top} devices is not "
            f"near-linear (bar: >= {0.6 * mesh_sizes[-1]:.1f}x)")
        acceptance = "pass"
    else:
        assert scaling >= 0.5, (
            f"CPU-sim multichip serving collapsed: {scaling:.2f}x of the "
            "1-device rate at the largest mesh")
        acceptance = "pending_tpu"

    report = {
        "schema": "kubebrain-multichip/v1",
        "metric": "multichip_rows_per_sec",
        "platform": platform_info(),
        "served_through_scheduler": True,
        "acceptance_near_linear_tpu": acceptance,
        **phase,
    }
    out_path = os.environ.get("KB_MULTICHIP_OUT")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
        print(f"[bench] wrote {out_path}", file=sys.stderr)
    print(json.dumps({
        "metric": "multichip_rows_per_sec",
        "value": rate,
        "unit": "rows/sec",
        "vs_baseline": round(rate / base, 3),
        "platform": platform_info(),
        "detail": {k: v for k, v in report.items() if k != "platform"},
    }))


def bench_watcurve() -> None:
    """Scan QPS vs the ``wat`` (read-replica) mesh axis — SURVEY P6.

    Blocks are sharded over ``part`` and REPLICATED over ``wat``; a batch of
    Q concurrent scan queries is sharded over ``wat`` so each replica group
    serves its own query subset. Reports the QPS curve for wat in {1,2,4,8}
    on the available mesh (8 virtual CPU devices in CI — the curve's SHAPE
    is the deliverable there; real chips give it real slope).
    Reference analogue: follower read replicas (README.md:21-24)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from kubebrain_tpu.ops import keys as keyops
    from kubebrain_tpu.ops.scan import visibility_mask
    from kubebrain_tpu.parallel.mesh import make_mesh

    n_keys = int(os.environ.get("KB_BENCH_KEYS", 50_000))
    revs = int(os.environ.get("KB_BENCH_REVS", 20))
    iters = int(os.environ.get("KB_BENCH_ITERS", 7))
    n_q = int(os.environ.get("KB_BENCH_QUERIES", 8))
    n_dev = len(jax.devices())

    chunks, rh, rl, tomb = build_dataset(n_keys, revs)
    n = len(chunks)
    # distinct per-query bounds: staggered sub-ranges of the key space
    starts, ends, qrevs = [], [], []
    for qi in range(n_q):
        lo = b"/registry/pods/default/pod-%08d" % (qi * (n_keys // n_q))
        hi = b"/registry/pods/default/pod-%08d" % ((qi + 1) * (n_keys // n_q))
        starts.append(pack_bound(lo))
        ends.append(pack_bound(hi))
        qrevs.append(n * (qi + 2) // (n_q + 2))
    s_q = np.stack(starts)
    e_q = np.stack(ends)
    qhi, qlo = keyops.split_revs(np.array(qrevs, dtype=np.uint64))

    curve = {}
    for wat in (1, 2, 4, 8):
        if n_dev % wat or wat > n_dev or n_q % wat:
            continue
        part = n_dev // wat
        mesh = make_mesh(axes=("part", "wat"), shape=(part, wat))
        rows_per = (n // part) // 8 * 8
        usable = rows_per * part
        P3, P1 = P("part", None, None), P("part", None)
        sh = lambda a, spec: jax.device_put(
            a, jax.sharding.NamedSharding(mesh, spec))
        keys_s = sh(chunks[:usable].reshape(part, rows_per, CHUNKS), P3)
        rh_s = sh(rh[:usable].reshape(part, rows_per), P1)
        rl_s = sh(rl[:usable].reshape(part, rows_per), P1)
        tomb_s = sh(tomb[:usable].reshape(part, rows_per), P1)
        nv_s = sh(np.full(part, rows_per, np.int32), P("part"))
        sq = sh(s_q, P("wat", None))
        eq = sh(e_q, P("wat", None))
        hq = sh(qhi, P("wat"))
        lq = sh(qlo, P("wat"))

        @partial_shard_map_scan(mesh)
        def scan_batch(keys, a, b, t, nv, ss, ee, hh, ll):
            def one_query(s1, e1, h1, l1):
                vis = jax.vmap(
                    lambda k, x, y, z, m: visibility_mask(
                        k, x, y, z, m, s1, e1, jnp.asarray(False), h1, l1)
                )(keys, a, b, t, nv)
                return jax.lax.psum(jnp.sum(vis, dtype=jnp.int32), "part")
            return jax.vmap(one_query)(ss, ee, hh, ll)

        out = scan_batch(keys_s, rh_s, rl_s, tomb_s, nv_s, sq, eq, hq, lq)
        jax.block_until_ready(out)
        lat = []
        for _ in range(iters):
            t0 = time.time()
            jax.block_until_ready(
                scan_batch(keys_s, rh_s, rl_s, tomb_s, nv_s, sq, eq, hq, lq))
            lat.append(time.time() - t0)
        p50 = sorted(lat)[len(lat) // 2]
        curve[wat] = round(n_q / p50, 1)

    base = curve.get(1) or 1.0
    best_wat = max(curve, key=curve.get)
    print(json.dumps({
        "metric": "scan QPS vs wat (read-replica axis)",
        "value": curve[best_wat],
        "unit": "queries/sec",
        "vs_baseline": round(curve[best_wat] / base, 3),
        "platform": platform_info(),
        "detail": {
            "curve_qps": {str(k): v for k, v in curve.items()},
            "queries": n_q, "rows": n, "devices": n_dev,
            "best_wat": best_wat,
            "note": "blocks replicated over wat, queries sharded over wat",
        },
    }))


def partial_shard_map_scan(mesh):
    """shard_map decorator for the wat-curve scan (part x wat mesh)."""
    import jax
    from jax.sharding import PartitionSpec as P

    def deco(f):
        return jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=(P("part", None, None), P("part", None), P("part", None),
                      P("part", None), P("part"),
                      P("wat", None), P("wat", None), P("wat"), P("wat")),
            out_specs=P("wat"),
        ))

    return deco


def main() -> None:
    n_keys = int(os.environ.get("KB_BENCH_KEYS", 200_000))
    revs = int(os.environ.get("KB_BENCH_REVS", 100))
    iters = int(os.environ.get("KB_BENCH_ITERS", 10))

    # No fallback: without a chip (and without an explicit CPU request) the
    # first device touch fails. KB_BENCH_PLATFORM=cpu / JAX_PLATFORMS=cpu is
    # CPU simulation, with 8 virtual devices so the sharded paths mean
    # something. Env only — jax is not imported yet.
    if os.environ.get("KB_BENCH_PLATFORM") == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    # unset, jax would fall back to the CPU with a warning when it finds no
    # chip — and a CPU number would be printed under a device metric's name
    os.environ.setdefault("JAX_PLATFORMS", "tpu")
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    from kubebrain_tpu.util.jaxcache import use_compile_cache

    use_compile_cache()

    metric = os.environ.get("KB_BENCH_METRIC", "scan")
    if metric == "fanout":
        return bench_fanout()
    if metric == "compact":
        return bench_compact()
    if metric == "insert":
        return bench_insert()
    if metric == "delete":
        return bench_delete()
    if metric == "grpc-insert":
        return bench_grpc_insert()
    if metric == "grpc-list":
        return bench_grpc_list()
    if metric == "sim":
        return bench_sim()
    if metric == "rebuild":
        return bench_rebuild()
    if metric == "sched":
        return bench_sched()
    if metric == "write":
        return bench_write()
    if metric == "cluster":
        return bench_cluster()
    if metric == "multichip":
        return bench_multichip()
    if metric == "watcurve":
        return bench_watcurve()

    import jax
    import jax.numpy as jnp

    from kubebrain_tpu.ops.scan import visibility_mask

    dev = jax.devices()[0]
    print(f"[bench] device: {dev}", file=sys.stderr)

    t0 = time.time()
    chunks, rh, rl, tomb = build_dataset(n_keys, revs)
    n = len(chunks)
    start = pack_bound(b"/registry/pods/")
    end = pack_bound(b"/registry/pods0")
    read_rev = np.uint64(n * 3 // 4)  # mid-history snapshot read
    qhi = np.uint32(read_rev >> np.uint64(32))
    qlo = np.uint32(read_rev & np.uint64(0xFFFFFFFF))
    print(f"[bench] dataset: {n_keys} keys x {revs} revs = {n} rows "
          f"({chunks.nbytes/1e9:.2f} GB keys) in {time.time()-t0:.1f}s", file=sys.stderr)
    keyenc_info = key_encoding_info(chunks)
    print(f"[bench] key encoding: {keyenc_info['encoded_key_bytes_per_row']}B/row "
          f"vs {keyenc_info['raw_key_bytes_per_row']}B raw = "
          f"{keyenc_info['key_compression_ratio']}x", file=sys.stderr)

    # ---- CPU baseline (vectorized numpy, same algorithm)
    t0 = time.time()
    cpu_visible = cpu_scan(chunks, rh, rl, tomb, start, end, qhi, qlo)
    cpu_dt = time.time() - t0
    cpu_rate = n / cpu_dt
    print(f"[bench] CPU numpy: {cpu_dt:.2f}s = {cpu_rate/1e6:.1f}M rows/s "
          f"(visible {cpu_visible})", file=sys.stderr)

    # ---- device kernel (jnp/XLA by default; KB_BENCH_PALLAS=1 for the
    # explicit chunk-major Pallas kernel; KB_BENCH_SHARDED=1 shards rows
    # over the full device mesh — BASELINE config 4's mesh-sharded scan)
    use_sharded = os.environ.get("KB_BENCH_SHARDED") == "1"
    if use_sharded:
        from kubebrain_tpu.ops.scan import visibility_mask as _vis
        from kubebrain_tpu.parallel.mesh import make_mesh, replicate, shard_rows

        mesh = make_mesh()
        n_dev = len(mesh.devices.reshape(-1))
        rows_per = (n // n_dev) // 8 * 8
        usable = rows_per * n_dev
        part = lambda a: shard_rows(mesh, a[:usable].reshape(n_dev, rows_per))
        keys_s = shard_rows(mesh, chunks[:usable].reshape(n_dev, rows_per, CHUNKS))
        rh_s, rl_s, tomb_s = part(rh), part(rl), part(tomb)
        nv = jax.device_put(
            np.full(n_dev, rows_per, np.int32),
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("part")),
        )
        s_r, e_r = replicate(mesh, start), replicate(mesh, end)

        @jax.jit
        def sharded_count(k, a, b, t, num):
            f = lambda kk, aa, bb, tt, nn: _vis(
                kk, aa, bb, tt, nn, s_r, e_r, jnp.asarray(False), qhi, qlo
            )
            return jnp.sum(jax.vmap(f)(k, a, b, t, num), dtype=jnp.int32)

        out = sharded_count(keys_s, rh_s, rl_s, tomb_s, nv)
        out.block_until_ready()
        lat = []
        for _ in range(iters):
            t0 = time.time()
            sharded_count(keys_s, rh_s, rl_s, tomb_s, nv).block_until_ready()
            lat.append(time.time() - t0)
        p50 = sorted(lat)[len(lat) // 2]
        rate = usable / p50
        print(json.dumps({
            "metric": "sharded range-scan keys/sec",
            "value": round(rate),
            "unit": "rows/sec",
            "vs_baseline": round(rate / cpu_rate, 3),
            "platform": platform_info(),
            "detail": {"rows": usable, "devices": n_dev,
                       "scan_p50_ms": round(p50 * 1e3, 2),
                       "cpu_numpy_rows_per_sec": round(cpu_rate)},
        }))
        return

    # On a TPU the Mosaic-lowered Pallas kernel is the default here; the jnp
    # kernel is the off-TPU / opt-out (KB_BENCH_PALLAS=0) path.
    on_tpu = dev.platform == "tpu"
    env_pallas = os.environ.get("KB_BENCH_PALLAS")
    use_pallas = on_tpu if env_pallas is None else env_pallas == "1"
    if use_pallas:
        from kubebrain_tpu.ops import scan_pallas as sp

        revs_u64 = ((rh.astype(np.uint64) << np.uint64(32)) | rl.astype(np.uint64))
        keys_t, rh31, rl31, tomb8, n_real = sp.prepare_blocks(chunks, revs_u64, tomb)
        qhi31, qlo31 = sp.split_revs31(np.array([int(read_rev)], dtype=np.uint64))
        s_f = sp.pack_bound_flipped(start)
        e_f = sp.pack_bound_flipped(end)
        p_args = [jax.device_put(jnp.asarray(x), dev) for x in (keys_t, rh31, rl31, tomb8)]
        p_bounds = [jax.device_put(jnp.asarray(x), dev) for x in (s_f, e_f)]

        interp = not on_tpu  # pallas needs interpret mode off-TPU

        @jax.jit
        def scan_count_pallas_sum(kt, a, b, t, s, e):
            mask = sp.scan_mask_pallas(
                kt, a, b, t, np.int32(n_real), s, e,
                np.int32(0), np.int32(qhi31[0]), np.int32(qlo31[0]),
                interpret=interp,
            )
            return jnp.sum(mask, dtype=jnp.int32)

        def scan_count(*_ignored):
            return scan_count_pallas_sum(*p_args, *p_bounds)

    else:
        @jax.jit
        def scan_count(keys, a, b, t, nv, s, e, hi, lo):
            mask = visibility_mask(keys, a, b, t, nv, s, e, jnp.asarray(False), hi, lo)
            return jnp.sum(mask, dtype=jnp.int32)

    if use_pallas:
        # the pallas closure ignores these; don't ship a second ~1.3GB
        # row-major copy of the dataset to HBM alongside the pallas layout
        d_args = [None] * 4
        s_dev = e_dev = nv = None
    else:
        d_args = [jax.device_put(x, dev) for x in (chunks, rh, rl, tomb)]
        s_dev, e_dev = jax.device_put(start, dev), jax.device_put(end, dev)
        nv = jnp.asarray(np.int32(min(n, 2**31 - 1)))
    t0 = time.time()
    out = scan_count(d_args[0], d_args[1], d_args[2], d_args[3], nv, s_dev, e_dev, qhi, qlo)
    out.block_until_ready()
    compile_dt = time.time() - t0
    tpu_visible = int(out)
    print(f"[bench] device first call (incl compile): {compile_dt:.1f}s, "
          f"visible {tpu_visible}", file=sys.stderr)
    assert tpu_visible == cpu_visible, f"device {tpu_visible} != cpu {cpu_visible}"

    lat = []
    for _ in range(iters):
        t0 = time.time()
        scan_count(d_args[0], d_args[1], d_args[2], d_args[3], nv, s_dev, e_dev, qhi, qlo).block_until_ready()
        lat.append(time.time() - t0)
    best = min(lat)
    p50 = sorted(lat)[len(lat) // 2]
    rate = n / p50
    print(f"[bench] device: best {best*1e3:.1f}ms p50 {p50*1e3:.1f}ms "
          f"= {rate/1e6:.1f}M rows/s", file=sys.stderr)

    # KB_TRACE=1: rerun the same scan under full span/stage tracing and
    # bound the tracer's cost on the north-star metric. Compared on
    # best-of-iters (noise-robust); the tracer's per-span cost is a few
    # monotonic() reads + list appends, so >5% means a regression in the
    # trace hot path, not machine jitter.
    trace_on = os.environ.get("KB_TRACE") == "1"
    trace_overhead = None
    if trace_on:
        from kubebrain_tpu.trace import TRACER

        TRACER.reset()

        # IDENTICAL work to the untraced loop (dispatch + block) — an extra
        # host pull here would measure a device-link round trip as "tracer
        # overhead" and fail the <5% assert spuriously
        def traced_scan():
            with TRACER.span("bench.scan"):
                with TRACER.stage("device_dispatch"):
                    out = scan_count(d_args[0], d_args[1], d_args[2],
                                     d_args[3], nv, s_dev, e_dev, qhi, qlo)
                with TRACER.stage("device_compute"):
                    jax.block_until_ready(out)

        lat_tr = []
        for _ in range(iters):
            t0 = time.time()
            traced_scan()
            lat_tr.append(time.time() - t0)
        trace_overhead = min(lat_tr) / best - 1
        print(f"[bench] traced: best {min(lat_tr)*1e3:.1f}ms "
              f"(overhead {trace_overhead:+.2%})", file=sys.stderr)
        assert trace_overhead < 0.05, (
            f"tracing overhead {trace_overhead:.1%} >= 5% "
            f"(traced best {min(lat_tr)*1e3:.2f}ms vs {best*1e3:.2f}ms)")

    # sustained throughput: jax dispatch is async, so issuing a burst and
    # blocking once amortizes the per-dispatch launch gap. This is the
    # concurrent-scan shape of the production scanner (many Range queries
    # in flight).
    BURST = 8
    t0 = time.time()
    outs = [scan_count(d_args[0], d_args[1], d_args[2], d_args[3], nv,
                       s_dev, e_dev, qhi, qlo) for _ in range(BURST)]
    jax.block_until_ready(outs)
    pipelined = n * BURST / (time.time() - t0)
    print(f"[bench] device pipelined x{BURST}: {pipelined/1e6:.1f}M rows/s",
          file=sys.stderr)

    # THE SERVING-PATH number: the same dispatches routed through the
    # request scheduler (kubebrain_tpu/sched) at bounded depth — what a
    # Range flood actually gets end to end. Each worker blocks on its own
    # result, so up to `depth` kernels are in flight (the pipelined shape
    # above), while admission, lanes, and coalescing stay on.
    from kubebrain_tpu.sched import RequestScheduler, SchedConfig

    depth = int(os.environ.get("KB_SCHED_DEPTH", 4))
    n_req = max(16, 2 * depth)
    sched = RequestScheduler(None, SchedConfig(depth=depth))
    try:
        def one_scan(i):
            return lambda: jax.block_until_ready(
                scan_count(d_args[0], d_args[1], d_args[2], d_args[3], nv,
                           s_dev, e_dev, qhi, qlo))
        # warm the scheduler threads once
        sched.submit(one_scan(-1))
        t0 = time.time()
        reqs = [sched.submit_async(one_scan(i), client=f"c{i % 4}")
                for i in range(n_req)]
        for r in reqs:
            r.wait(300.0)
        scheduled = n * n_req / (time.time() - t0)
    finally:
        sched.close()
    print(f"[bench] scheduled x{n_req} depth {depth}: "
          f"{scheduled/1e6:.1f}M rows/s", file=sys.stderr)

    # QUERY-BATCHED dispatch (ISSUE 5): the same scheduler concurrency over
    # 8 DISTINCT prefix ranges, but a freed dispatch slot drains every
    # compatible ready request and launches ONE query-batched kernel for
    # the whole set — the kernel-launch amortization the scheduler's
    # pipelining alone can't buy (each pipelined request still pays its own
    # launch). Acceptance on TPU: >= 1.5x the scheduled rate at the same
    # concurrency, byte-identical per-query results; on the CPU dry run:
    # byte-identical and within 10% of sequential.
    NQ = 8
    # distinct bounds: the dataset's key-space octile borders (real rows)
    q_rows = [(n * i) // NQ for i in range(NQ)]
    if use_pallas:
        q_starts = np.stack([sp.pack_bound_flipped(chunks[r]) for r in q_rows])
        q_ends = np.stack(
            [sp.pack_bound_flipped(chunks[(n * (i + 1)) // NQ - 1])
             for i in range(NQ - 1)] + [q_starts[0]])
        q_unb = np.array([0] * (NQ - 1) + [1], dtype=np.int32)
        q_his = np.full(NQ, np.int32(qhi31[0]), dtype=np.int32)
        q_los = np.full(NQ, np.int32(qlo31[0]), dtype=np.int32)

        @jax.jit
        def count_one_q(kt, a, b, t, s_, e_, u_):
            mask = sp.scan_mask_pallas(
                kt, a, b, t, np.int32(n_real), s_, e_, u_,
                np.int32(qhi31[0]), np.int32(qlo31[0]), interpret=interp)
            return jnp.sum(mask, dtype=jnp.int32)

        @jax.jit
        def count_many_q(kt, a, b, t, ss, ee, uu, hh, ll):
            mask = sp.scan_mask_pallas_q(
                kt, a, b, t, np.int32(n_real), ss, ee, uu, hh, ll,
                interpret=interp)
            return jnp.sum(mask, axis=1, dtype=jnp.int32)

        def one_count(k):
            return count_one_q(*p_args, jnp.asarray(q_starts[k]),
                               jnp.asarray(q_ends[k]), np.int32(q_unb[k]))

        def many_counts(ks):
            return count_many_q(
                *p_args, jnp.asarray(q_starts[ks]), jnp.asarray(q_ends[ks]),
                jnp.asarray(q_unb[ks]), jnp.asarray(q_his[ks]),
                jnp.asarray(q_los[ks]))
    else:
        from kubebrain_tpu.ops.scan import visibility_mask_queries

        q_starts = np.stack([chunks[r] for r in q_rows])
        q_ends = np.stack([chunks[(n * (i + 1)) // NQ - 1]
                           for i in range(NQ - 1)] + [q_starts[0]])
        q_unb = np.array([False] * (NQ - 1) + [True])
        q_his = np.full(NQ, qhi, dtype=np.uint32)
        q_los = np.full(NQ, qlo, dtype=np.uint32)

        @jax.jit
        def count_one_q(keys, a, b, t, num, s_, e_, u_):
            mask = visibility_mask(keys, a, b, t, num, s_, e_, u_, qhi, qlo)
            return jnp.sum(mask, dtype=jnp.int32)

        @jax.jit
        def count_many_q(keys, a, b, t, num, ss, ee, uu, hh, ll):
            masks = visibility_mask_queries(
                keys, a, b, t, num, ss, ee, uu, hh, ll)
            return jnp.sum(masks, axis=1, dtype=jnp.int32)

        def one_count(k):
            return count_one_q(d_args[0], d_args[1], d_args[2], d_args[3], nv,
                               jnp.asarray(q_starts[k]),
                               jnp.asarray(q_ends[k]), jnp.asarray(bool(q_unb[k])))

        def many_counts(ks):
            return count_many_q(
                d_args[0], d_args[1], d_args[2], d_args[3], nv,
                jnp.asarray(q_starts[ks]), jnp.asarray(q_ends[ks]),
                jnp.asarray(q_unb[ks]), jnp.asarray(q_his[ks]),
                jnp.asarray(q_los[ks]))

    def batch_exec(descs):
        """Scheduler batch executor: range indices -> per-query counts from
        ONE kernel launch (pow2-padded like TpuScanner._dev_mask_batch)."""
        ks = list(descs)
        qp = 1
        while qp < len(ks):
            qp *= 2
        counts = np.asarray(many_counts(np.array(ks + [ks[0]] * (qp - len(ks)))))
        return [int(counts[j]) for j in range(len(ks))]

    # warm + per-query oracle (sequential single dispatches)
    expect_q = [int(one_count(k)) for k in range(NQ)]
    batch_exec(list(range(NQ)))  # compile the Q=8 shape off the clock
    t0 = time.time()
    for i in range(n_req):
        int(one_count(i % NQ))
    seq_q_dt = time.time() - t0

    # distinct ranges through the scheduler, one dispatch each (baseline)
    sched = RequestScheduler(None, SchedConfig(depth=depth, batch=1))
    try:
        sched.submit(lambda: int(one_count(0)))  # warm the worker threads
        t0 = time.time()
        reqs = [sched.submit_async(
            lambda k=i % NQ: int(one_count(k)), client=f"c{i % 4}")
            for i in range(n_req)]
        got_sched = [r.wait(300.0) for r in reqs]
        sched_q_dt = time.time() - t0
    finally:
        sched.close()
    scheduled_q = n * n_req / sched_q_dt
    assert all(got_sched[i] == expect_q[i % NQ] for i in range(n_req))

    # the same requests with batch formation on: slots plugged so every
    # ready request queues, then one release -> n_req/NQ batched launches
    sched = RequestScheduler(None, SchedConfig(depth=depth, batch=NQ))
    try:
        import threading as _threading
        release = _threading.Event()
        for _ in range(depth):
            sched.submit_async(release.wait)
        time.sleep(0.05)
        reqs = [sched.submit_async(
            lambda k=i % NQ: batch_exec([k])[0], client=f"c{i % 4}",
            bargs=i % NQ, bexec=batch_exec) for i in range(n_req)]
        t0 = time.time()
        release.set()
        got_batched = [r.wait(300.0) for r in reqs]
        batched_dt = time.time() - t0
    finally:
        sched.close()
    batched = n * n_req / batched_dt
    mism = sum(1 for i in range(n_req) if got_batched[i] != expect_q[i % NQ])
    assert mism == 0, f"{mism}/{n_req} batched results diverged"
    print(f"[bench] batched x{n_req} ({NQ} distinct ranges/launch): "
          f"{batched/1e6:.1f}M rows/s ({batched/scheduled_q:.2f}x scheduled, "
          f"batched riders {sched.batched})", file=sys.stderr)
    if on_tpu:
        # the PR 5 bar: the record below is printed either way, then a miss
        # fails the run
        bar_1_5x = "pass" if batched >= 1.5 * scheduled_q else "fail"
    else:
        bar_1_5x = "pending_tpu"
        # CPU dry run: the batched path must cost ~the same total compute
        tol = float(os.environ.get("KB_BENCH_BATCH_TOL", "1.10"))
        assert batched_dt <= seq_q_dt * tol, (
            f"CPU batched path {batched_dt:.3f}s vs sequential "
            f"{seq_q_dt:.3f}s (> {tol:.0%})")

    # per-stage time fractions from the tracer's EWMAs: device stages from
    # the traced single-dispatch run, queue_wait from the scheduled run
    # (the scheduler records it for every request)
    stage_breakdown = None
    if trace_on:
        from kubebrain_tpu.trace import TRACER

        ew = {
            "queue_wait": TRACER.ewma("queue_wait") or 0.0,
            "dispatch": TRACER.ewma("device_dispatch") or 0.0,
            "device": TRACER.ewma("device_compute") or 0.0,
            "host_copy": TRACER.ewma("host_copy") or 0.0,
        }
        total_ew = sum(ew.values()) or 1.0
        stage_breakdown = {k: round(v / total_ew, 4) for k, v in ew.items()}

    print(json.dumps({
        "metric": "range-scan keys/sec",
        "value": round(rate),
        "unit": "rows/sec",
        "vs_baseline": round(rate / cpu_rate, 3),
        "platform": platform_info(),
        "detail": {
            "rows": n, "visible": tpu_visible,
            "scan_p50_ms": round(p50 * 1e3, 2),
            "pipelined_rows_per_sec": round(pipelined),
            "pipelined_depth": BURST,
            "scheduled_rows_per_sec": round(scheduled),
            "scheduled_depth": depth,
            "scheduled_vs_single_dispatch": round(scheduled / rate, 3),
            "scheduled_distinct_rows_per_sec": round(scheduled_q),
            "batched_rows_per_sec": round(batched),
            "batched_queries_per_launch": NQ,
            "batched_vs_scheduled": round(batched / scheduled_q, 3),
            "batched_byte_identical": True,
            # the PR 5 acceptance bar (>= 1.5x scheduled at 8 distinct
            # prefixes) is a TPU bar: on CPU dispatch isn't the bottleneck,
            # so the run only proves byte-identity + cost parity and the
            # bar stays machine-visibly pending until a real-TPU round
            "batched_acceptance_1_5x": bar_1_5x,
            "cpu_numpy_rows_per_sec": round(cpu_rate),
            "device": str(dev),
            "kernel": "pallas" if use_pallas else "jnp",
            # mirror-compression capacity unlock on this dataset's keyspace
            # (kubebrain-keyenc/v1; tracked across BENCH rounds)
            "mirror_bytes_per_row": keyenc_info["mirror_bytes_per_row"],
            "key_compression_ratio": keyenc_info["key_compression_ratio"],
            "key_encoding": keyenc_info,
            **({"stage_breakdown": stage_breakdown,
                "trace_overhead": round(trace_overhead, 4)}
               if trace_on else {}),
        },
    }))
    if bar_1_5x == "fail":
        sys.exit(f"batched {batched/1e6:.1f}M rows/s < 1.5x scheduled "
                 f"{scheduled_q/1e6:.1f}M rows/s at {NQ} distinct ranges")


if __name__ == "__main__":
    main()
