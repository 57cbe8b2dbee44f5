"""The serving benchmark (see BENCHMARK.json and PERF.md)."""
