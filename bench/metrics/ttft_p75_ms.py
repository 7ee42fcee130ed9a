"""Time to first token, 75th percentile (the highest that about 40 requests support with ten beyond it) over the requests due in the window."""

import functools

from bench.readers import ttft_ms

read = functools.partial(ttft_ms, q=75)
