"""The benchmark: everything `BENCHMARK.json` reads lives in this directory."""
