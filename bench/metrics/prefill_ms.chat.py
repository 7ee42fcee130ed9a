"""Decode step: mean time from admission to first token (the
teacher-forced prefill passes), from the engine's own stamps."""

from bench.stages import prefill_ms as read  # noqa: F401
