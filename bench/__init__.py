"""The on-chip benchmark: one harness, cells found by name in
BENCHMARK.json (see bench/harness.py and bench/run.py)."""
