"""Chip benchmark of sealed serving: see BENCHMARK.json and PERF.md."""
