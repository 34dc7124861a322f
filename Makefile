PY      := python
PYPATH  := PYTHONPATH=src:.

.PHONY: test test-slow bench-smoke bench check-regression lint

## tier-1 verification (what CI runs)
test:
	PYTHONPATH=src $(PY) -m pytest -x -q

## includes the slow FL end-to-end / dry-run subprocess tests
test-slow:
	PYTHONPATH=src $(PY) -m pytest -q --run-slow

## fast benchmark smoke: kernels + latency figures + engine throughput
## + cross-size aggregation comparison + codec sweep + service load
## + population-scale simulation + mesh-sharded engine scaling
## + traced-run observability schema check + fleet health report
bench-smoke:
	$(PYPATH) $(PY) benchmarks/run.py --quick --only kernels,roofline,latency,cross_size,comm,serve,population,mesh,obs,health

## bench-regression gate: fail if any policy's sync-relative time-to-target
## regressed >25% vs the committed baseline (see benchmarks/check_regression.py)
check-regression:
	$(PYPATH) $(PY) benchmarks/check_regression.py

## full paper-figure benchmark sweep (slow)
bench:
	$(PYPATH) $(PY) benchmarks/run.py

## syntax check + import smoke (no third-party linters in the container)
lint:
	$(PY) -m compileall -q src tests benchmarks examples
	PYTHONPATH=src $(PY) -c "import repro, repro.fl, repro.fl.batched, \
repro.fl.sharded, repro.comm, repro.core, repro.core.nested, \
repro.core.population, repro.data, repro.kernels, repro.kernels.sharded, \
repro.models, repro.launch, repro.launch.mesh, \
repro.launch.compile_cache, repro.obs, \
repro.obs.rl, repro.obs.health, repro.obs.slo, repro.obs.export, \
repro.obs.report, repro.optim, repro.serve, repro.service, repro.sim, \
repro.train, repro.utils.proptest"
	@echo lint OK
