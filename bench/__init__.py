"""On-chip benchmark: cells, configurations, traffic and metric readers
found by name from BENCHMARK.json (see PERF.md)."""
