"""The chip benchmark: see harness.py and BENCHMARK.json."""
