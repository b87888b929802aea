"""Where the persistent XLA compilation cache lives.

One rule for every entry point (serve, bench.py, chip_smoke.py,
tests/conftest.py): where ``JAX_COMPILATION_CACHE_DIR`` is set, it is
left alone and nothing else is set in code; where it is unset, the cache
is ONE fixed git-ignored directory at the root of the checkout. The path
is part of the cache key's lookup, so a directory that moves (a pid, a
version string, ``tempfile``, ``~``) never hits.

Every program is cached, however small or quick to compile: a warm
start then compiles nothing it has seen, and the
``/jax/compilation_cache/cache_{hits,misses}`` events count every
program (``obs/xlamon.py``).

Call :func:`configure` BEFORE the first ``import jax`` of the process:
jax reads these variables once, at import. This module imports no jax.
"""

from __future__ import annotations

import os
import pathlib

CACHE_DIRNAME = ".jax_cache"


def default_dir() -> str:
    """``<checkout>/.jax_cache`` — the directory that holds the
    ``gyeeta_tpu`` package."""
    return str(pathlib.Path(__file__).resolve().parents[2] / CACHE_DIRNAME)


def configure(env=None) -> str:
    """Default the cache variables in ``env`` (``os.environ``) and
    return the cache directory in force."""
    env = os.environ if env is None else env
    if not env.get("JAX_COMPILATION_CACHE_DIR"):
        env["JAX_COMPILATION_CACHE_DIR"] = default_dir()
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    return env["JAX_COMPILATION_CACHE_DIR"]
