"""Train host loop: host time in Trainer.step() outside the wait for the
step's metrics, per step, from the trainer's own clock."""

from bench.train_readers import host_ms_per_step as read  # noqa: F401
