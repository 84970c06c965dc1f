"""On-chip benchmark of the tuner: see BENCHMARK.json and PERF.md."""
