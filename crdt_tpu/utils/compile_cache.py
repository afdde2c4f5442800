"""Where JAX's persistent compilation cache lives — one rule for every
entry point (the daemon, bench.py, benches/bench_baseline.py,
chip_smoke.py), called before the first compile.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; the cache lives
  there and no other directory is set in code.
* Otherwise: ``<checkout>/.jax_cache`` — a fixed path (never built from a
  temp name, a pid or the time: the path is part of what makes a later
  run hit), gitignored.

Every compile is cached, however short: a cold chip run compiles dozens
of merge shapes of a second or two each, and those are what a second run
must find again.  The CPU backend keeps the cache off (see :func:`enable`).
"""
from __future__ import annotations

import os
import pathlib
from typing import Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> str:
    """The directory :func:`enable` points JAX at."""
    return os.environ.get(ENV) or str(DEFAULT_DIR)


def enable() -> Optional[str]:
    """Turn the persistent cache on at :func:`cache_dir` and return it;
    None (cache left off) on the CPU backend, where runs are rehearsals
    whose compiles take seconds and XLA:CPU warns on every reloaded
    entry.  Initializes the backend: call it after the platform is
    chosen."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    path = cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
