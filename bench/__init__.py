"""On-chip benchmark harness (see BENCHMARK.json and bench/run.py)."""
