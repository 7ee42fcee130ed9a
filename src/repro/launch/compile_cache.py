"""Where JAX's persistent compilation cache lives for the entry points.

Called at the start of every launcher's ``main()`` and of
``chip_smoke.py`` — never on ``import repro``: tests compile for
described (not attached) TPUs, and those entries must not land in a cache
that a later run would try to read back.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
this helper sets nothing.  Otherwise the cache goes to ``.jax_cache/`` at
the checkout root — a fixed path, because the path is part of the cache
key, so a directory that moved would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
