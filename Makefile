# Build / test entry points (reference analogue: makefile +
# build/build-*.sh; engine choice is a runtime flag here, not a build tag).

SHELL := /bin/bash  # test-tier1 needs pipefail

.PHONY: all native test run clean protos lint typecheck check test-tier1 \
        chip-smoke

all: native

# Static analysis: the kblint syntactic rules (KB101-KB111) over all
# Python PLUS the interprocedural tier (--deep: call graph over
# kubebrain_tpu/ + tools/, rules KB112-KB115, baseline.json),
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

# The on-chip check (PERF.md): the served --storage=tpu path end to end on
# the attached TPU, both scan kernels, answers compared with the smoke's own
# oracle and /metrics held to "the DEVICE answered". No JAX_PLATFORMS pin:
# the smoke gives its server child JAX_PLATFORMS=tpu and FAILS without a
# chip. One process per chip — nothing else may hold it meanwhile.
chip-smoke:
	python chip_smoke.py

run: native
	python -m kubebrain_tpu.cli --single-node --storage=tpu --inner-storage=native

clean:
	$(MAKE) -C native clean
