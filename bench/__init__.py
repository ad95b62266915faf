"""The chip benchmark of Faaslet inference: harness, yardstick and data.

``bench/run.py`` is the entry point named in ``BENCHMARK.json``."""
