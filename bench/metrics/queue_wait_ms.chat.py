"""Scheduler and KV pool: mean wait from submit to admission, from the
engine's own admission stamp."""

from bench.stages import queue_wait_ms as read  # noqa: F401
