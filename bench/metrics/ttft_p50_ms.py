"""Time to first token, median over the requests due in the window."""

import functools

from bench.readers import ttft_ms

read = functools.partial(ttft_ms, q=50)
