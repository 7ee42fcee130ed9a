"""Tokens processed in the window over the window."""

from bench.readers import serve_tokens_per_s as read  # noqa: F401
