"""Gap between consecutive output tokens, 95th percentile over the window."""

import functools

from bench.readers import itl_ms

read = functools.partial(itl_ms, q=95)
