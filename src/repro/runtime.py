"""Process set-up shared by the entry points (chip_smoke.py,
benchmarks/run.py, launch/serve.py). Nothing here runs at import time:
each entry point calls ``enable_compile_cache`` once, before its first
compile."""

from __future__ import annotations

import os
import pathlib

#: The compile cache's fixed home when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: ``.jax_cache/`` at the root of the checkout (git-ignored). A
#: fixed path matters: the cache key includes it, so a directory named
#: after a temp dir, a pid or the time would never hit.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: it is
    used as it is and no other directory is set in code. Otherwise the
    cache goes to ``CACHE_DIR``."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
