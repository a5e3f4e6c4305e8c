# Parity with the reference's Makefile (Makefile:1-18): `test` runs the
# whole suite with concurrency hygiene, plus this repo's report/proto
# targets. The benchmark is `python3 benchmarks/run.py` (BENCHMARK.json).

.PHONY: test test-fast lint lockmap sanitize scenarios capacity-report profile-report ledger-report soak chaos proto docker clean native

# the suite runs on a virtual 8-device CPU mesh (tests/conftest.py)
test:
	python -m pytest tests/ -q

test-fast: lint
	python -m pytest tests/ -q -x -m "not slow"

# guberlint: AST-driven invariant analyzer (docs/static-analysis.md).
# Zero unwaived findings is a tier-1 gate (tests/test_lint.py runs the
# same check in-process).
lint: lockmap
	python -m gubernator_tpu.analysis

# lock acquisition-order graph: drift-gate the built graph against the
# committed lockmap.json in both directions and fail on any unwaived
# lock-order/donation-flow finding (docs/static-analysis.md "Reading a
# lockmap"); after a reviewed ordering change:
# `python scripts/lockmap_report.py --write` and commit
lockmap:
	python scripts/lockmap_report.py --check

# TSan/ASan/UBSan builds of native/*.cpp into the same hash-keyed .so
# cache `make native` uses; the TSan variants load under
# TSAN_OPTIONS=suppressions=native/tsan.supp (tests/test_tsan.py)
sanitize:
	python scripts/build_native.py --sanitize

# scenario atlas: seeded workload drills against live 1-2 node clusters,
# SLO verdicts written to the round's SCEN_r<NN>.json; exits 1 on any
# FAIL (docs/OPERATIONS.md "Scenario drills"); PROFILE=full for the
# real-length shapes
scenarios:
	python scripts/scenario_report.py --profile $(or $(PROFILE),short)

# occupancy, headroom forecast, hit-mass concentration and top-K heavy
# hitters from a running node's /v1/debug/{keyspace,history} endpoints
# (docs/OPERATIONS.md "Capacity planning"); ADDR defaults to 127.0.0.1:80
capacity-report:
	python scripts/capacity_report.py $(ADDR)

# serving-cycle decomposition, lock-wait sites and kernel cost table
# from a running node's /v1/debug/{profile,kernels} endpoints
# (docs/OPERATIONS.md "Performance triage"); ADDR defaults to 127.0.0.1:80
profile-report:
	python scripts/profile_report.py $(ADDR)

# decision-ledger conservation digest: admits-by-authority, minted lease
# budget, over-admission distribution and the device ground-truth check
# (docs/OPERATIONS.md "Over-admission triage"); ADDR defaults to 127.0.0.1:80
ledger-report:
	python scripts/ledger_report.py $(ADDR)

# 30s fault-injection soak: kill/restart chaos under load, invariant-judged
soak:
	PYTHONPATH=. python scripts/soak.py

# deterministic fault-injection drills (circuit breaker, degraded-local,
# recovery) with a randomized seed; -s keeps the seed line visible —
# reproduce any failure with GUBER_CHAOS_SEED=<seed> make chaos
chaos:
	@seed=$${GUBER_CHAOS_SEED:-$$(od -An -N2 -tu2 /dev/urandom | tr -d ' ')}; \
	echo "chaos seed: $$seed"; \
	GUBER_CHAOS_SEED=$$seed python -m pytest tests/ -q -s -m chaos

# rebuild both native components (keydir.cpp, peerlink.cpp) plus their
# tsan variants from source into the hash-keyed .so cache names the
# loaders expect; stale caches are deleted. tests/test_native_build.py is
# the tier-1 drift check (a cached .so built from other source fails).
native:
	python scripts/build_native.py

proto:
	bash scripts/genproto.sh

docker:
	docker build -t gubernator-tpu:latest .

clean:
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null; true
	rm -f gubernator_tpu/native/_*.so gubernator_tpu/native/.build.lock
