"""The benchmark: one command runs one cell (BENCHMARK.json) once."""
