"""Device: share of the traced window with no operation running."""

from bench.readers import idle_share as read  # noqa: F401
