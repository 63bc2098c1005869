"""The benchmark of the served BLS verify path (see BENCHMARK.json, PERF.md)."""
