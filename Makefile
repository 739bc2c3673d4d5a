# Top-level gate (reference Makefile:48-75 + test/test.make discipline):
# `make test` chains lint, spec-drift, the native build, the TSAN stream
# test, and the full pytest suite — one command answers "is the tree good".
#
# `make demo` / `make start` / `make stop` run the local demo cluster
# (reference test/start-stop.make:1-92): certs + registry + controller +
# feeder daemon on localhost, with the README quickstart driven end to end.

PY ?= python
RUFF := $(shell command -v ruff 2>/dev/null)

.PHONY: test pytest lint drift proto native tsan demo start stop clean replication-demo trace-demo chaos quorum-smoke

# drift and tsan are standalone conveniences; the full pytest target
# already runs both (SpecDrift + the TSAN stream test build in-fixture).
test: lint native pytest

pytest:
	$(PY) -m pytest tests/ -q

drift:
	$(PY) -m pytest tests/test_common.py -q -k SpecDrift

# Regenerate oim.proto + oim_pb2.py from spec.md, then prove the tree is
# drift-free: the one command to run after editing the ```proto block.
proto:
	$(PY) scripts/gen_proto.py
	$(PY) -m pytest tests/test_common.py -q -k SpecDrift

lint:
ifdef RUFF
	ruff check .
else
	$(PY) scripts/lint.py
endif

native:
	$(MAKE) -C native

tsan:
	$(MAKE) -C native tsan
	$(PY) -m pytest tests/test_staging.py -q -k thread_sanitizer

# Chaos ladder (minutes): seeded, scripted fault schedules over an
# in-process cluster sim — replica SIGKILL, black-holed channel,
# page-pool exhaustion, registry-primary kill -> auto-promotion,
# controller kill -> feeder failover + warm-standby cache hit, draft
# collapse -> spec-valve fallback, and the compound rung (promotion
# while a replica drains while the prefix-holder dies). Every rung
# asserts CONVERGENCE: the expected heal events on /debug/events, in
# order; zero client-visible errors where the retry contract promises
# them; byte-identical routed outputs; zero-leak page/prefix/channel
# censuses. Same seed -> same heal-event sequence, or a loud assert
# (`python -m oim_tpu.chaos --seed N` picks another). The fast rungs
# run in tier-1 as tests/test_chaos_smoke.py.
chaos:
	env JAX_PLATFORMS=cpu $(PY) -m oim_tpu.chaos

# Quorum-registry acceptance loop (seconds): 3 in-process members
# elect a leader, a quorum-committed write is readable on a follower
# and refused BY a follower, the leader is SIGKILLed and writes resume
# on the survivors' new leader with zero human intervention, and a
# Watch stream opened before the kill survives it (re-targets, resume
# token honored or snapshot-resynced, no missed rows). Also runs in
# tier-1 as tests/test_quorum_smoke.py.
quorum-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_quorum_smoke.py -q

demo:
	bash scripts/demo_cluster.sh demo

# Replicated-registry failover demo: primary + standby + 1 controller on
# localhost; SIGKILLs the primary and shows the standby auto-promote.
replication-demo:
	bash scripts/replication_demo.sh demo

# Distributed-tracing demo: registry + controller + feeder one-window run
# with --trace-dir; merges the per-process Chrome traces and fails unless
# one trace_id spans >= 3 processes. Artifacts in _demo_trace/.
trace-demo:
	$(PY) scripts/trace_demo.py

start:
	bash scripts/demo_cluster.sh start

stop:
	bash scripts/demo_cluster.sh stop

clean:
	$(MAKE) -C native clean
	rm -rf _demo _demo_repl _demo_trace
