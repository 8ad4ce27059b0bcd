"""The benchmark: one command per cell of BENCHMARK.json (see README.md)."""
