"""Train step: one step's least time at the four chips' peaks over the mean
step time."""

from bench.train_readers import step_mfu as read  # noqa: F401
