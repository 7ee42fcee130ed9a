"""Dist: collective time with no compute running, over the traced window,
for the chip with the most."""

from bench.train_readers import collective_share as read  # noqa: F401
