# Build / test / bench entry points (reference analogue: makefile +
# build/build-*.sh; engine choice is a runtime flag here, not a build tag).

SHELL := /bin/bash  # test-tier1 needs pipefail

.PHONY: all native test bench bench-all bench-smoke bench-cluster \
        bench-multichip bench-write bench-compact bench-fanout run clean \
        protos lint typecheck check test-tier1 chip-smoke

all: native

# Static analysis: the kblint syntactic rules (KB101-KB111) over all
# Python PLUS the interprocedural tier (--deep: call graph over
# kubebrain_tpu/ + tools/ + bench.py, rules KB112-KB115, baseline.json),
# then the native lint pass. The deep run is held to a 60s wall-clock
# budget (exceeded = failure) and is incremental via .kblint_cache/
# (content-hash keyed; KBLINT_CACHE=0 disables). docs/static_analysis.md.
lint:
	python -m tools.kblint kubebrain_tpu tools tests --deep --budget 60
	$(MAKE) -C native lint

# mypy over the typed core when installed; compileall fallback otherwise
# (this container must not pip install anything).
typecheck:
	python tools/typecheck.py

# The ROADMAP.md tier-1 verify command, the ONE definition CI and
# tools/ci.sh both invoke (the flags and timeout must not drift apart).
test-tier1:
	set -o pipefail; rm -f /tmp/_t1.log; \
	timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
	    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
	    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; \
	rc=$$?; \
	echo "DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c)"; \
	exit $$rc

# Everything CI runs: lint + typecheck + the tier-1 suite (tools/ci.sh).
check:
	tools/ci.sh

native:
	$(MAKE) -C native

protos:
	cd kubebrain_tpu/proto && protoc --python_out=. kv.proto rpc.proto brain.proto health.proto

test: native
	python -m pytest tests/ -q

bench: native
	python bench.py

bench-all: native
	python bench.py
	KB_BENCH_METRIC=fanout python bench.py
	KB_BENCH_METRIC=compact python bench.py
	KB_BENCH_METRIC=insert python bench.py

# The on-chip check (PERF.md): the served --storage=tpu path end to end on
# the attached TPU, both scan kernels, answers compared with the smoke's own
# oracle and /metrics held to "the DEVICE answered". No JAX_PLATFORMS pin:
# the smoke gives its server child JAX_PLATFORMS=tpu and FAILS without a
# chip. One process per chip — nothing else may hold it meanwhile.
chip-smoke:
	python chip_smoke.py

# CPU-sim. Scheduler microbench on a tiny dataset (no native build needed):
# asserts scheduled == unscheduled byte-identically, reports coalescing
# and shed counters. Fast enough for CI smoke.
bench-smoke:
	JAX_PLATFORMS=cpu KB_BENCH_METRIC=sched KB_BENCH_KEYS=2000 \
	    KB_BENCH_OPS=200 python bench.py

# CPU-sim (REPLICAS>0 and SCENARIO=watch_heavy start 2-3 JAX servers at
# once, and a chip belongs to one process: CPU-sim ONLY until servers can
# be pinned to their own chips, ROADMAP R7(b)).
# Cluster-scale workload replay (kubebrain_tpu/workload): deterministic
# kube-apiserver traffic for an N-node simulated cluster through the real
# gRPC front — pod churn + controller list/watch + node lease keepalives +
# compaction in one run. Emits WORKLOAD_rNN.json (docs/workloads.md).
# Same seed => byte-identical op trace (self-checked every run).
# MESH_PART/SCAN_PARTS drive a part-sharded server (STORAGE=tpu required;
# docs/multichip.md), e.g.: make bench-cluster N=1000 STORAGE=tpu MESH_PART=8
# SCENARIO=churn_heavy skews the trace to pod churn + a keepalive storm
# (write-group commit exercised + asserted; docs/writes.md).
# SCENARIO=watch_heavy skews to multi-controller fan-in (many watchers per
# namespace prefix, thin writes) and spawns every server — leader and
# followers — with the block-batched device fan-out matcher; with
# REPLICAS=2 the whole watcher population rides the followers
# (docs/watch.md). MESH_WAT=<n> additionally shards the watcher table
# over n (simulated) devices, any scenario.
# FAULTS=<preset> (smoke|storage|watch|merge|full) arms chaos mode
# (docs/faults.md): churn_heavy replayed against a fault-injected server,
# judged by the acknowledged-write consistency check; emits CHAOS_rNN.json.
# COMPACT_S overrides the spec's compaction cadence in SIMULATED seconds
# (0 = scenario default), e.g. the 5-min-compaction scenario of the
# ROADMAP: make bench-cluster N=1000 DURATION=900 COMPACT_S=300.
# REPLICAS=<n> spawns n follower replicas next to the leader
# (docs/replication.md): controller list+watch traffic routes to the
# followers (bounded-staleness local serving + local watch fan-out),
# writes/leases round-robin and forward; emits REPLICA_rNN.json with the
# per-replica served/forwarded/lag section. FAULTS=replica REPLICAS=2
# arms the follower chaos kinds (replication reset, leader-unreachable,
# fence timeout) and judges by the same acked-write consistency check.
N ?= 1000
STORAGE ?= memkv
MESH_PART ?= 0
SCAN_PARTS ?= 0
SCENARIO ?= cluster
FAULTS ?= none
FAULT_SEED ?= 0
COMPACT_S ?= 0
REPLICAS ?= 0
MESH_WAT ?= 0
bench-cluster:
	JAX_PLATFORMS=cpu KB_BENCH_METRIC=cluster KB_BENCH_NODES=$(N) \
	    KB_WORKLOAD_STORAGE=$(STORAGE) KB_WORKLOAD_MESH_PART=$(MESH_PART) \
	    KB_WORKLOAD_SCAN_PARTITIONS=$(SCAN_PARTS) \
	    KB_WORKLOAD_SCENARIO=$(SCENARIO) KB_WORKLOAD_FAULTS=$(FAULTS) \
	    KB_WORKLOAD_FAULT_SEED=$(FAULT_SEED) \
	    KB_WORKLOAD_COMPACT_S=$(COMPACT_S) \
	    KB_WORKLOAD_REPLICAS=$(REPLICAS) \
	    KB_WORKLOAD_MESH_WAT=$(MESH_WAT) python bench.py

# CPU-sim. Watch fan-out bench (docs/watch.md): block-batched device matching at
# 10k+ watchers — watch_fanout_events_per_sec, delivery masks asserted
# byte-identical to the host segment-index oracle, batched path >= 2x the
# per-batch device path on CPU-sim (TPU bar pending_tpu off-TPU). Emits
# the kubebrain-fanout/v1 report to KB_FANOUT_OUT (FANOUT_rNN.json).
bench-fanout:
	JAX_PLATFORMS=cpu KB_BENCH_METRIC=fanout python bench.py

# CPU-sim (8 virtual devices). Multichip sharded serving curve
# (docs/multichip.md): the scan workload
# served through the scheduler at mesh sizes 1..8, byte-identical across
# sizes; KB_MULTICHIP_OUT=MULTICHIP_rNN.json writes the schema'd report.
bench-multichip:
	JAX_PLATFORMS=cpu KB_BENCH_METRIC=multichip python bench.py

# CPU-sim. Write-path group commit (docs/writes.md): write_txns_per_sec serial vs
# grouped at 8-writer concurrency (grouped >= 1.5x asserted on CPU,
# byte-identity vs the sequential oracle), plus the TPU-engine steady
# state proving the incremental delta merge never takes a full rebuild.
bench-write:
	JAX_PLATFORMS=cpu KB_BENCH_METRIC=write python bench.py

# CPU-sim. Device-side compaction (docs/compaction.md): the stored-domain pipeline
# vs the engine-generic host compactor over one ~1M-row store with a
# realistic victim mix — byte-identity vs the sequential oracle asserted,
# zero full rebuilds / re-dictionary encodes asserted, >= 2x host asserted
# at acceptance size (CPU-sim; TPU bar pending_tpu off-TPU). Emits the
# kubebrain-compact/v1 report to KB_COMPACT_OUT (COMPACT_rNN.json).
bench-compact:
	JAX_PLATFORMS=cpu KB_BENCH_METRIC=compact python bench.py

run: native
	python -m kubebrain_tpu.cli --single-node --storage=tpu --inner-storage=native

clean:
	$(MAKE) -C native clean
