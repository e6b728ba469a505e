# Tier-1 verification (ROADMAP.md): must pass from a fresh checkout.
PY ?= python

.PHONY: test test-scenarios test-workers test-durability bench-dispatch \
	bench-smoke trace-smoke serve-example docs-check

test:
	PYTHONPATH=src $(PY) -m pytest -x -q

# The deterministic scheduling-scenario suites (fake clock + scripted
# traces driving the real dispatcher): preemption ordering, SLO admission
# control, load shedding.  A subset of `make test`, callable on its own
# for fast iteration on the dispatch plane; pytest-timeout (or the
# conftest SIGALRM fallback) bounds every test, so a wedged scenario
# fails instead of hanging.
test-scenarios:
	PYTHONPATH=src $(PY) -m pytest -x -q \
		tests/test_preemption.py tests/test_slo.py \
		tests/test_dispatch_properties.py

# The multi-process worker-plane suite (failure matrix over spawn AND
# fork) under a hard wall-clock bound, plus a leaked-process check:
# pytest runs in-process inside tools/run_worker_tests.py, so any worker
# a test failed to reap is still that interpreter's child and
# multiprocessing.active_children() catches it exactly — the job fails
# on a leak even when every test passed.
test-workers:
	PYTHONPATH=src timeout 600 $(PY) tools/run_worker_tests.py

# The durability suite (lifecycle state machine, journal, crash
# recovery, fault injection — including two real-process SIGKILL
# kill-and-restart tests) under a hard wall-clock bound, plus TWO leak
# checks: multiprocessing.active_children() for plane workers spawned
# in-process, and a /proc cmdline scan for the SIGKILLed child
# dispatcher's orphaned worker grandchildren (which are nobody's
# multiprocessing children).  The job fails on a leak even when every
# test passed.
test-durability:
	PYTHONPATH=src timeout 900 $(PY) tools/run_durability_tests.py

docs-check:
	$(PY) tools/check_docs.py

bench-dispatch:
	PYTHONPATH=src $(PY) -m benchmarks.dispatch_bench

# CI-sized grant-path measurement: the kilo-tenant row reduced to 64
# tenants over deterministic tick engines (no model compiles).  Exits
# non-zero on token divergence, wakeups-per-grant > 2, or a non-flat
# per-grant CPU ratio; CI additionally bounds the step with a hard
# timeout.
bench-smoke:
	PYTHONPATH=src $(PY) -m benchmarks.dispatch_bench --smoke

# bench-smoke with the span tracer enabled: exports the Chrome trace and
# exits non-zero if the JSON fails structural validation or records no
# step spans (plus every bench-smoke gate above).
trace-smoke:
	PYTHONPATH=src $(PY) -m benchmarks.dispatch_bench --smoke \
		--trace-out /tmp/repro-trace-smoke.json

serve-example:
	PYTHONPATH=src $(PY) examples/serve_llm.py --smoke --requests 8 --max-new 6
