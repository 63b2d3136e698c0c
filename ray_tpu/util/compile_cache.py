"""Where JAX's persistent compilation cache lives.

Every process that compiles calls ``enable()`` before it does: worker
start-up, the test session, ``chip_smoke.py``.  A directory
given from outside in ``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting
and is left alone.  Otherwise the cache goes to one fixed, git-ignored
directory inside the checkout: the path is part of the cache key, so a
directory that moves never hits.
"""

from __future__ import annotations

import os
import sys

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Make sure this process and its children compile into a persistent
    cache; returns the directory.  Never imports jax: a process that has
    not imported it yet (a worker, a driver that stays off the chip) picks
    the directory up from the environment when it does."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    os.environ[ENV] = DEFAULT_DIR
    jax = sys.modules.get("jax")
    if jax is not None:  # imported before us: it has already read the env
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
