"""Process start to the window's start: weights, compile or cache load, warm-up."""

from bench.readers import setup_s as read  # noqa: F401
