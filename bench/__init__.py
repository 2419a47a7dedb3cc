"""Chip benchmark of the policy engine: see BENCHMARK.json and PERF.md."""
