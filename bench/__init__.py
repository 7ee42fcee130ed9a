"""The benchmark: one runner, its drivers, traffic, metrics and yardstick.

See ``bench/run.py`` and ``PERF.md``."""
