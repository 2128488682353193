"""The benchmark: harness, yardstick and data.  See ``PERF.md``."""
