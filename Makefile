# Operator entry points (analog of the reference's Makefile:33-34
# test/build targets).  The framework is Python+C++: "build" compiles
# the native codec and generated protobuf in place; "install" does a
# pip install of the package with the pilosa-tpu console script.

PYTHON ?= python

.PHONY: default test lint analyze typecheck metrics-lint check chaos-smoke device-chaos-smoke load-smoke resize-smoke multichip-smoke tier-smoke replication-smoke subscribe-smoke ingest-smoke sparse-smoke churn-soak gameday gameday-smoke install build docker clean generate

default: build test

# Full test suite on the virtual 8-device CPU mesh (tests/conftest.py
# forces the backend; never touches a real TPU).
test:
	$(PYTHON) -m pytest tests/ -q

# ruff F,E,W,B,UP across the package (configured in pyproject.toml).
# Skips with a notice when ruff isn't installed (the slim dev
# container); CI always installs it, so the gate is real there.
lint:
	@if $(PYTHON) -m ruff --version >/dev/null 2>&1; then \
		$(PYTHON) -m ruff check pilosa_tpu/; \
	else \
		echo "lint: ruff not installed; skipping (CI enforces)"; \
	fi

# The concurrency & compile-hazard analyzer (pilosa_tpu/analyze):
# lock-order graph + cycles, blocking-calls-under-lock, JAX compile-key
# hazards, leaked scoped resources.  Allowlist lives in analyze.toml;
# exits non-zero on any undocumented finding.  BLOCKING in check/CI.
analyze:
	$(PYTHON) -m pilosa_tpu.analyze --json analyze-report.json

# Metrics-documentation lint (tools/metrics_lint.py): AST-extracts
# every metric name from the stats calls in pilosa_tpu/ and fails if
# one is absent from the docs/administration.md metrics reference
# table.  BLOCKING in CI (.github/workflows/check.yml).
metrics-lint:
	$(PYTHON) tools/metrics_lint.py

# mypy non-strict baseline (pyproject [tool.mypy]): the promoted
# modules (exec/plan, device/pool, net/resilience, analyze/*) check
# for real; everything else must import-check.  Skips with a notice
# when mypy isn't installed; CI installs it, so blocking there.
typecheck:
	@if $(PYTHON) -c "import mypy" >/dev/null 2>&1; then \
		$(PYTHON) -m mypy; \
	else \
		echo "typecheck: mypy not installed; skipping (CI enforces)"; \
	fi

# The CI gate (.github/workflows/check.yml): lint + analyzer + types
# plus the tier-1 test suite (everything not marked slow) on the
# forced CPU backend.
check: lint analyze typecheck metrics-lint
	env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

# Compile the C++ codec and verify the wire module imports.
build:
	$(PYTHON) -c "from pilosa_tpu import native; assert native.available(), 'native build failed'; print('native codec ok')"
	$(PYTHON) -c "from pilosa_tpu.net import wire_pb2; print('wire protobuf ok')"

install:
	$(PYTHON) -m pip install .

# Tiny CPU chaos pass: two in-process nodes under PILOSA_FAULTS (one
# erroring + one delayed RPC leg); a fan-out query must still answer
# exactly.  Non-blocking in CI (.github/workflows/check.yml).
chaos-smoke:
	$(PYTHON) tools/chaos_smoke.py

# Device-fault chaos pass (tools/device_chaos_smoke.py): on the virtual
# 8-device mesh, a mixed Count/Range/TopN/Sum storm under EACH injected
# device fault (oom / error / hang) must answer byte-identically via
# host fallback, the device must quarantine within the configured
# threshold, a hung collective must trip the launch watchdog instead of
# wedging the process, and clearing the fault must heal through a
# half-open probe.  BLOCKING in CI (.github/workflows/check.yml).
device-chaos-smoke:
	$(PYTHON) tools/device_chaos_smoke.py

# Tiny CPU open-loop load pass (tools/load_smoke.py over the
# tools/load_harness.py storm generator): asserts the artifact carries
# the goodput-vs-offered-load curve + shed counters, and that shed-rate
# is 0 at trivial load.  Writes load-report.json (uploaded as a CI
# artifact).  Non-blocking in CI (.github/workflows/check.yml).
load-smoke:
	$(PYTHON) tools/load_smoke.py

# Tiny CPU live-resize pass (tools/resize_smoke.py): two real nodes
# grow to three under a concurrent writer; asserts checksummed query
# results before == after, zero dropped writes, the new node owns
# slices, and the sources released theirs.  BLOCKING in CI
# (.github/workflows/check.yml), alongside chaos-smoke.
resize-smoke:
	$(PYTHON) tools/resize_smoke.py

# Mesh data-plane smoke (tools/multichip_smoke.py): virtual 8-device
# CPU mesh; asserts sharded execution engages BY DEFAULT with >1
# device visible, a distinct-query Intersect+Count storm + TopN
# through the coalescer/fusion path (incl. the ICI-reduced "total"
# launch) answers byte-identically to the forced single-device path
# and a numpy oracle, fragment planes spread over the shards, and
# interp program-cache entries stay within bounds.  BLOCKING in CI
# (.github/workflows/check.yml).
multichip-smoke:
	$(PYTHON) tools/multichip_smoke.py

# Tiered-storage smoke (tools/tier_smoke.py): local-FS object store;
# demote under a forced disk budget -> cold-boot a node from an EMPTY
# data dir + store alone -> byte-check Count/TopN/Range vs the donor
# (/debug/tier showing cold->hydrating->hot) -> retention sweep ages
# and deletes time-quantum views with a racing writer reviving one.
# BLOCKING in CI (.github/workflows/check.yml), like resize-smoke.
tier-smoke:
	$(PYTHON) tools/tier_smoke.py

# Quorum-replication smoke (tools/replication_smoke.py): 3-node
# replica-3 write storm at consistency=quorum with one replica KILLED
# mid-storm -> restart -> breaker-triggered hint replay converges
# checksums with zero lost writes and NO anti-entropy tick; a
# consistency=all write against the dead replica fails loudly.
# BLOCKING in CI (.github/workflows/check.yml), like resize-smoke.
replication-smoke:
	$(PYTHON) tools/replication_smoke.py

# Standing-query smoke (tools/subscribe_smoke.py): two real nodes,
# 100+ standing PQL subscriptions (single-row counts, compound trees,
# TopN) under a live import stream; grows the cluster to three nodes
# MID-STREAM, then asserts every subscription converges to the pull
# oracle, updates are version-monotonic, the topology move re-stamped
# subscription epochs, and update lag p99 stays bounded.  CI also runs
# it under PILOSA_LOCK_CHECK=1.  BLOCKING in CI
# (.github/workflows/check.yml), like resize-smoke.
subscribe-smoke:
	$(PYTHON) tools/subscribe_smoke.py

# Durable-ingest smoke (tools/ingest_smoke.py): a child process takes
# a multi-threaded acked write storm (each ack reported only after the
# WAL group commit fsynced) and is kill -9'd mid-storm; reopening the
# data dir must replay the WAL tail with ZERO lost acked bits vs the
# parent's host oracle.  CI runs it under PILOSA_LOCK_CHECK=1.
# BLOCKING in CI (.github/workflows/check.yml), like subscribe-smoke.
ingest-smoke:
	$(PYTHON) tools/ingest_smoke.py

# Compressed-plane smoke (tools/sparse_smoke.py): tiny 1%-density
# clustered corpus on the CPU backend; write-time container selection
# must pick RLE/sparse formats (no dense rows), every answer over the
# compressed planes is byte-checked against a numpy oracle with the
# anchored position-domain count route engaged, and the paged-in rows'
# resident HBM must sit >= 10x below logical dense geometry.  CI runs
# it under PILOSA_LOCK_CHECK=1.  BLOCKING in CI
# (.github/workflows/check.yml), like subscribe-smoke.
sparse-smoke:
	$(PYTHON) tools/sparse_smoke.py

# The everything-at-once soak (tools/gameday.py): one seeded run
# composing every failure mode the stack claims to survive — a
# multi-tenant fairness storm (victim p99 bounded while the hot tenant
# sheds on quota), a kill -9'd replica recovering via WAL replay +
# hint drain with zero lost acked writes, resize 2->3->2 under load
# with a WINDOWED device-fault timeline and tier demote/hydrate,
# subscription convergence across both cutovers, and gossip under
# datagram loss.  Emits gameday.json; non-blocking soak lane in CI,
# with the --smoke variant blocking.
gameday:
	$(PYTHON) tools/gameday.py --artifact gameday.json

gameday-smoke:
	$(PYTHON) tools/gameday.py --smoke --artifact gameday.json

# Gossip churn soak (tools/churn_soak.py): 20-50 virtual members under
# seeded datagram loss + member flapping; asserts membership converges
# on exactly the live set each cycle with zero false-DOWNs of
# reachable members.  The deterministic tier-1 slice lives in
# tests/test_churn.py; this is the big dial-a-size soak.
churn-soak:
	$(PYTHON) tools/churn_soak.py

docker:
	docker build -t pilosa-tpu .

# Regenerate wire_pb2.py from the wire contract (needs protoc).
generate:
	protoc --python_out=. pilosa_tpu/net/wire.proto

clean:
	rm -f pilosa_tpu/native/libpilosa_native.so pilosa_tpu/native/libpilosa_native.so.flags
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
