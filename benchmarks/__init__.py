"""The benchmark: everything `BENCHMARK.json` names lives here (see README.md)."""
