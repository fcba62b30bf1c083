#!/usr/bin/env bash
# The offline CI entry point (mirrored by .github/workflows/check.yml):
#   1. make lint        — kblint project invariants (syntactic KB101-KB111
#                         + the funnel-confinement rules KB116/KB117/KB127
#                         + the --deep interprocedural tier KB112-KB122
#                         + the CFG/typestate leak tier KB123-KB126,
#                         zero non-baselined findings, <60s budget
#                         enforced) + native lint, then the kblint engine
#                         self-tests (rule fixtures, differential corpus,
#                         leak-rule corpus, cache cold/warm) — a lint-engine
#                         regression should fail before anything else runs
#   2. make typecheck   — mypy (or compileall fallback)
#   3. scheduler gate   — sched semantics + query-batched scan tests
#                         (batched == sequential byte-identical, incl. the
#                         batched Pallas kernel cases) + the write-path
#                         group commit under the field-write sanitizer;
#                         fast, and a scheduler regression should fail
#                         before the long tier-1 run, not 10 minutes into it
#   4. observability    — trace/span tests + a live-server smoke: one Range
#                         must populate /debug/traces and the
#                         kb_rpc_stage_seconds histogram
#   5. lease subsystem  — TTL state machine + revision-stamped expiry
#                         (a lease regression silently breaks apiserver
#                         event TTLs; fail before the long tier-1 run)
#   6. workload replay  — generator determinism (same seed => byte-identical
#                         op trace), SLO report schema, and a small-N
#                         end-to-end replay through the real gRPC front
#                         with client/server /metrics reconciliation
#   7. multichip+encode — sharded serving on 8 simulated host devices
#                         (conftest's xla_force_host_platform_device_count):
#                         sharded-vs-single byte identity, O(visible-rows)
#                         host transfer, dirty-shard-only republish, the
#                         served dry run (mesh {1, n} byte-identical
#                         through the scheduler), and the encoded-mirror
#                         differential suite
#                         (encoded == raw byte-identity incl. overlays,
#                         adversarial bounds, pallas-vs-jnp, P=N/P=2N)
#   8. compaction       — device-side stored-domain compaction
#                         (docs/compaction.md): differential vs the
#                         engine-generic compactor, victim-only decode,
#                         dirty-shard-only republish, retry→escalate,
#                         and the full-rebuild rung against the same
#                         generic-engine oracle
#   9. replica          — read scale-out (docs/replication.md): follower
#                         fence-read correctness (byte-identical to the
#                         leader under concurrent writers), bounded-
#                         staleness refusal + the degradation ladder,
#                         watch resume across a replication reset, the
#                         TPU-mirror identity at pinned revisions, and a
#                         small two-replica end-to-end smoke through the
#                         real gRPC front
#  10. chaos (FAULTS)   — deterministic fault injection (docs/faults.md):
#                         schedule sha determinism, FAULTS=none inertness
#                         byte-identity, the storage error classes through
#                         a live Backend (definite/uncertain + group-commit
#                         demux + FIFO read-back repair), mirror quarantine/
#                         merge-retry/escalation, watch resume (no lost or
#                         duplicated events across server-side resets), and
#                         a small FAULTS=smoke replay asserting the
#                         acknowledged-write consistency invariant
#  11. watch fan-out    — block-batched dispatch (docs/watch.md): device
#                         deliver byte-identical to the brute-force and
#                         segment-index oracles under churn, the sharded
#                         wat-table identity on 8 simulated devices,
#                         NUL-bound single-key exactness, overflow regrow,
#                         version-regression rebuild, the hub's block
#                         route, and KB127 confinement self-tests (via
#                         step 1)
#  12. tier-1 pytest    — the ROADMAP.md verify command
# Run from anywhere; operates on the repo this script lives in.

set -uo pipefail

cd "$(dirname "$0")/.."

echo "=== [1/12] make lint (syntactic + deep interprocedural, 60s budget)"
make lint || exit 1
env JAX_PLATFORMS=cpu python -m pytest tests/test_kblint.py \
    tests/test_kblint_deep.py tests/test_kblint_races.py \
    tests/test_kblint_leaks.py \
    -q -m 'not slow' -p no:cacheprovider || exit 1

echo "=== [2/12] make typecheck"
make typecheck || exit 1

echo "=== [3/12] scheduler semantics + query-batched scan + write group commit (+ field-write sanitizer)"
env JAX_PLATFORMS=cpu python -m pytest tests/test_sched.py \
    tests/test_sched_batch.py tests/test_scan_pallas.py \
    tests/test_write_batch.py -q -m 'not slow' \
    -p no:cacheprovider || exit 1
# runtime field-write sanitizer smoke (docs/static_analysis.md): the
# concurrency-heavy write-path module under KB_FIELDCHECK=1 — the
# instrumented __setattr__ path must neither break the suite nor record
# ungated multi-thread no-common-guard writes on the tracked classes
env JAX_PLATFORMS=cpu KB_FIELDCHECK=1 KB_FIELDCHECK_STRICT=1 \
    python -m pytest tests/test_write_batch.py -q -m 'not slow' \
    -p no:cacheprovider || exit 1

echo "=== [4/12] request tracing: span tests + live-server /debug/traces smoke"
env JAX_PLATFORMS=cpu python -m pytest tests/test_trace.py -q -m 'not slow' \
    -p no:cacheprovider || exit 1
env JAX_PLATFORMS=cpu python tools/smoke_trace.py || exit 1

echo "=== [5/12] lease subsystem: TTL state machine + revision-stamped expiry"
env JAX_PLATFORMS=cpu python -m pytest tests/test_lease.py -q -m 'not slow' \
    -p no:cacheprovider || exit 1

echo "=== [6/12] workload replay: determinism + SLO schema + small-N gRPC smoke"
env JAX_PLATFORMS=cpu python -m pytest tests/test_workload.py -q -m 'not slow' \
    -p no:cacheprovider || exit 1

echo "=== [7/12] multichip sharded serving + encoded mirror: identity + transfer budget + served dry-run"
env JAX_PLATFORMS=cpu python -m pytest tests/test_multichip.py \
    tests/test_encode.py \
    tests/test_graft_entry.py -q -m 'not slow' -p no:cacheprovider || exit 1

echo "=== [8/12] device-side compaction: stored-domain differential + victim-only decode + full-rebuild rung"
env JAX_PLATFORMS=cpu python -m pytest tests/test_compact_device.py \
    tests/test_compact_faults.py -q -m 'not slow' \
    -p no:cacheprovider || exit 1

echo "=== [9/12] replica: fence reads + bounded staleness + watch resume + two-replica gRPC smoke"
env JAX_PLATFORMS=cpu python -m pytest tests/test_replica.py -q -m 'not slow' \
    -p no:cacheprovider || exit 1

echo "=== [10/12] chaos: fault-schedule determinism + inertness + classification + FAULTS=smoke consistency gate"
env JAX_PLATFORMS=cpu python -m pytest tests/test_faults.py \
    tests/test_watch_robustness.py -q -m 'not slow' \
    -p no:cacheprovider || exit 1
# chaos under the full sanitizer umbrella (docs/static_analysis.md): the
# fault-injection suite with lockcheck + fieldcheck + leakcheck all armed
# and strict — exception paths under injected faults must not leak dealt
# revisions, slots, watchers, or spans (the KB123-KB126 runtime twin)
env JAX_PLATFORMS=cpu KB_SANITIZE=1 KB_SANITIZE_STRICT=1 \
    python -m pytest tests/test_faults.py -q -m 'not slow' \
    -p no:cacheprovider || exit 1

echo "=== [11/12] watch fan-out: block-batched dispatch differentials + sharded wat table + hub block route"
env JAX_PLATFORMS=cpu python -m pytest tests/test_fanout_device.py \
    tests/test_fanout_integration.py -q -m 'not slow' \
    -p no:cacheprovider || exit 1

echo "=== [12/12] tier-1 tests (ROADMAP.md verify, one definition: make test-tier1)"
exec make test-tier1
