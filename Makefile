# CI analog of the reference's Makefile (Makefile:44-70: per-package
# unit tests, -race variants, lint) for a no-external-deps environment.

PY ?= python

.PHONY: test test-race chaos lint verify all

all: lint test

test:
	$(PY) -m pytest tests/ -q

# Race-amplified run: tests/conftest.py lowers the interpreter's thread
# switch interval to force frequent preemption at the concurrency seams
# (the Go -race analog available to pure Python — races surface as
# corrupted state/assertions in the stress tests rather than reports).
test-race:
	VPP_TPU_RACE=1 $(PY) -m pytest tests/test_concurrency.py tests/test_io.py \
		tests/test_native_ring.py tests/test_kvserver.py \
		tests/test_vcl_preload.py tests/test_multihost_unit.py \
		tests/test_kvstore_fencing.py -q

# Seeded fault-injection schedules (ISSUE 8): kvstore partitions,
# ring fault → dispatch fallback, dispatch fetch/tx faults, torn
# snapshots, reconnect storms — each asserting exact packet/session
# conservation after recovery. Seeds default inside the tests
# (override: VPPT_CHAOS_SEED=n); bounded runtime; also marked `slow`
# so the tier-1 `-m 'not slow'` timing budget never pays for it.
chaos:
	$(PY) -m pytest tests/test_chaos.py -q -m chaos

# Base style pass + the pure-AST analysis passes (tools/analysis/):
# --jax tracer/recompile hygiene, --threads lock discipline,
# --partitions rule completeness (pure import, no jax arrays), and the
# ISSUE 20 device-boundary dataflow passes: --uploads group-staleness,
# --transfers host-fetch allowlisting, --donate use-after-donate. The
# registry passes (--metrics/--counters/--tables) import jax, so
# tier-1 runs them from tests instead (test_exposition / test_acl_bv).
lint:
	$(PY) tools/lint.py --jax --threads --partitions --uploads \
		--transfers --donate

