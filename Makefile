PY ?= python

.PHONY: lint typecheck sentinel test test-fast trace-demo chaos service-chaos clean-native

lint:
	$(PY) tools/lint.py

# mypy strict on the typed core (deequ_tpu/lint, deequ_tpu/observe —
# see [tool.mypy] in pyproject.toml), permissive elsewhere. Degrades to
# a notice when mypy is not installed: the repo must stay checkable in
# environments that cannot add packages.
typecheck:
	@if $(PY) -c "import mypy" 2>/dev/null; then \
		$(PY) -m mypy deequ_tpu/lint deequ_tpu/observe; \
	else \
		echo "typecheck: mypy not installed — skipping (pip install mypy to enable)"; \
	fi

# regression sentinel: anomaly strategies over the engine telemetry
# series (ENGINE_METRICS.json) and the committed BENCH_r0*.json
# history; exits nonzero when throughput or phase shares regress
sentinel:
	JAX_PLATFORMS=cpu $(PY) tools/sentinel.py

trace-demo:
	JAX_PLATFORMS=cpu PYTHONPATH=.:examples $(PY) examples/tracing_example.py

# seeded fault matrix (ISSUE 13): the chaos harness's injection
# schedule determinism + retry/cancel/watchdog semantics, the chaos
# differential (IO errors, short reads, corrupt pages, worker deaths,
# stalls -> bit-identical on both placements), the SIGKILL-resume
# test, and the injected-fault shutdown audits
chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_faults.py -q
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_suite_differential_fuzz.py -q -k "chaos or sigkill"
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_pipeline_shutdown.py -q -k "injected or cancellation"

# fleet-service fault matrix (ISSUE 14): seeded chaos on the four
# service.* points (admission, queue pop, worker, scheduler tick) with
# cross-tenant blast-radius containment asserted bit-identically, plus
# the full service unit/integration suite (admission codes, quotas,
# breakers, preempt->resume bit-identity, drain audits)
service-chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_service_chaos.py -q
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_service.py -q
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_pipeline_shutdown.py -q -k "service"

# remove cached native builds (the hash-named .so files): any strays in
# the package tree from older versions plus the per-user cache dir the
# build now prefers
clean-native:
	rm -f deequ_tpu/ops/native/_deequ_native_*.so
	$(PY) -c "from deequ_tpu.ops.native import per_user_cache_dir as d; \
	import glob, os; p = d(); \
	[os.unlink(f) for f in (glob.glob(os.path.join(p, '_deequ_native_*.so')) if p else [])]; \
	print('clean-native:', p or '(no user cache dir)')"

test: lint
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q

test-fast:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' -x
