"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so the directory must not move
between runs: a temporary name, a pid or a time would never hit.  An
operator who sets ``JAX_COMPILATION_CACHE_DIR`` decides alone (JAX reads
the variable itself); otherwise compiled programs land in a fixed
``<repo>/.jax_cache`` (listed in ``.gitignore``).  Call before the
process's first compile: JAX settles its cache on that compile.
"""

from __future__ import annotations

import os
import sys

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its fixed place; return the
    directory in use.  Importing jax costs a second or more, so a
    process that has not imported it gets the environment variable,
    which jax reads when it is imported (spawned workers inherit it)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir",
                                         CACHE_DIR)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    return CACHE_DIR
