"""Decode step: the passes' least time at the chip's peaks over their host time."""

from bench.readers import serve_step_mfu as read  # noqa: F401
