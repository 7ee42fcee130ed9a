"""Decode step: host time in engine.step() per pass."""

from bench.readers import pass_ms as read  # noqa: F401
