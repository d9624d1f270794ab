# Tier-1: the correctness gate — must stay NO WORSE than the seed
# baseline (the pass count of the last accepted run).
# Tier-2: cheap perf smoke for PRs touching the hot paths — refreshes
# benchmarks/out/BENCH_portfolio.json on a tiny matrix in <60s.

PY := PYTHONPATH=src python

.PHONY: test test-device test-host test-exact test-big test-chaos \
	test-chaos-flake test-obs test-mapping test-sharded bench \
	bench-smoke planner-smoke verify

test:
	$(PY) -m pytest -x -q

# jax-engine / device fan-out tests only (the `device` pytest marker)
test-device:
	$(PY) -m pytest -x -q -m "device and not big"

# everything but the device tests (quick CPU-only signal)
test-host:
	$(PY) -m pytest -x -q -m "not device and not big"

# the exact-solver stack (HiGHS ILP; self-skips where scipy.milp is absent)
test-exact:
	$(PY) -m pytest -x -q -m ilp

# big-instance regressions: over-the-dense-envelope instances streamed
# through the blocked longest-path form (deselected from tier-1)
test-big:
	$(PY) -m pytest -x -q -m big

# chaos drills: scripted fault injection against the PlanService
# degradation ladder + worker supervision (deselected from tier-1;
# deterministic per seed). Runs the whole suite under BOTH a single
# drain worker and a 4-worker pool — supervision, requeue, and
# bit-identity must hold at every worker count.
test-chaos:
	$(PY) -m pytest -x -q -m chaos --chaos-workers 1
	$(PY) -m pytest -x -q -m chaos --chaos-workers 4

# flake guard: the 4-worker chaos suite repeated across 3 seed offsets —
# catches interleaving-dependent failures the single deterministic run
# can miss
test-chaos-flake:
	for seed in 0 1 2; do \
	  $(PY) -m pytest -x -q -m chaos --chaos-workers 4 \
	    --chaos-seed $$seed || exit 1; \
	done

# observability subsystem: tracing/metrics primitives, the service's
# registry-backed stats(), Prometheus exposition, journal compaction
test-obs:
	$(PY) -m pytest -x -q tests/test_obs.py

# mapping subsystem: HEFT seeds, neighborhood moves, joint search
# quality chain, mapping-mode request validation, service integration
test-mapping:
	$(PY) -m pytest -x -q tests/test_mapping.py

# multi-device sharded grid under 8 forced virtual host devices: the
# shard_map launch must stay bitwise-identical to single-device (the
# flag must land before jax initializes, hence the explicit env here —
# the test module also sets it at import for plain `pytest` runs)
test-sharded:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  $(PY) -m pytest -x -q tests/test_sharded_grid.py

bench:
	$(PY) -m benchmarks.run --only portfolio

bench-smoke:
	$(PY) -m benchmarks.run --only portfolio --smoke

planner-smoke:
	$(PY) -c "from repro.api import LocalSearchConfig, Planner, \
	PlanRequest, PlanResult, PlanningSession; print('planner api: ok')"

# the PR gate: tier-1 tests + chaos drills + observability suite +
# mapping suite + sharded-grid suite + Planner import smoke + tier-2
# bench refresh
verify: test test-chaos test-obs test-mapping test-sharded planner-smoke \
	bench-smoke
