"""Scheduler and KV pool: lanes that advanced per decode pass."""

from bench.readers import lanes_per_pass as read  # noqa: F401
