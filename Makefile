PYTHON ?= python
export PYTHONPATH := src:$(PYTHONPATH)

.PHONY: test test-fast bench-smoke bench-sharding bench-combine \
	bench-multihost bench-shuffle bench-serving bench-streaming \
	serve-smoke lint check

# tier-1 verify (ROADMAP.md)
test:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -x -q

# quick signal: core engine + system + planner only
test-fast:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -x -q tests/test_engine.py tests/test_scheduler.py \
	    tests/test_system.py tests/test_planner.py tests/test_channels.py

bench-smoke:
	$(PYTHON) -m benchmarks.run --only pipeline_cache

bench-sharding:
	$(PYTHON) -m benchmarks.sharded_scan --json sharded_scan.json

bench-combine:
	$(PYTHON) -m benchmarks.shard_combine --json shard_combine.json

bench-multihost:
	$(PYTHON) -m benchmarks.multihost_scan --json multihost_scan.json

bench-shuffle:
	$(PYTHON) -m benchmarks.shuffle_exchange --json shuffle_exchange.json

bench-serving:
	$(PYTHON) -m benchmarks.serving_gateway --json BENCH_serving.json \
		--metrics-json BENCH_serving_metrics.json

bench-streaming:
	$(PYTHON) -m benchmarks.streaming_chain --json BENCH_streaming.json

serve-smoke:
	$(PYTHON) -m repro.launch.serve --arch xlstm-125m --smoke --steps 8 --batch 2

lint:
	$(PYTHON) -m compileall -q src tests benchmarks examples
	@if command -v ruff >/dev/null 2>&1; then ruff check src/repro; \
	else echo "ruff not installed; skipping (CI lint job runs it pinned)"; fi
	@if command -v mypy >/dev/null 2>&1; then mypy; \
	else echo "mypy not installed; skipping (CI lint job runs it pinned)"; fi

# plan-time static analysis: repo-internal lock lint + AST lint of the
# example pipelines (pure AST — nothing is imported or executed)
check:
	$(PYTHON) -m repro.analysis --internal
	$(PYTHON) -m repro.analysis examples
