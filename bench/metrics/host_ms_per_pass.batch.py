"""Engine host loop: host time in engine.step() outside the wait for a
pass's tokens, per pass, from the engine's own clock."""

from bench.stages import host_ms_per_pass as read  # noqa: F401
