"""Scheduler and KV pool: prompt tokens mapped from shared pages over prompt tokens admitted."""

from bench.readers import prefix_reuse_share as read  # noqa: F401
