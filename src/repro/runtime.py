"""JAX process settings shared by every device path.

* :func:`x64` — the one scoped 64-bit switch.  The device replay keeps
  clocks in float64 and byte counters in int64, and the exact ``jnp``
  scoring backend sorts int64 offsets; both open this scope around their
  device calls.  Outside it the process keeps JAX's 32-bit default, so
  the int32 Pallas kernels and everything else are unaffected.
* :func:`use_compile_cache` — JAX's persistent compilation cache.  Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
  is set here; otherwise the cache sits at :data:`DEFAULT_CACHE_DIR`, one
  fixed path inside the checkout (the path is part of the cache key, so a
  directory that moved between runs would never hit).
"""

from __future__ import annotations

import contextlib
import os
from pathlib import Path
from typing import Iterator

import jax

#: ``<checkout>/.jax_cache`` — the compile cache when the environment
#: names none.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


@contextlib.contextmanager
def x64() -> Iterator[None]:
    """Scoped 64-bit mode (``jax.enable_x64(True)``) for the block."""

    with jax.enable_x64(True):
        yield


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
