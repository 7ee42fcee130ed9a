"""Load generator: how late requests were submitted after their due time, 99th percentile."""

from bench.readers import gen_late_p99_ms as read  # noqa: F401
