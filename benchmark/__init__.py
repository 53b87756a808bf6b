"""The repo's benchmark: BENCHMARK.json names what is in here."""
