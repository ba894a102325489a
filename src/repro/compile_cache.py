"""JAX's persistent compilation cache for the entry points (chip_smoke.py,
launch/serve.py, launch/train.py, benchmarks/run.py). Library import and the
tests set no cache; only an entry point calls :func:`enable`."""

from __future__ import annotations

import os
import pathlib

#: Fixed, inside the checkout: the cache path is part of JAX's cache key, so
#: a directory that moved between runs would never hit.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Where compiled programs are kept. ``JAX_COMPILATION_CACHE_DIR``, when
    set, wins and nothing is set in code (JAX reads the variable itself);
    otherwise the cache goes to :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
