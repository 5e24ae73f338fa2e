"""Where JAX keeps its persistent compilation cache for this repo's
entry point ``chip_smoke.py``.

``JAX_COMPILATION_CACHE_DIR``, when set, is the place: JAX reads it
itself and nothing else is set.  Otherwise the cache lives at
``<repo>/.jax_cache`` — a fixed path, because the path is part of what
a later run must find again (listed in ``.gitignore``).
"""

from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one place and
    return that path.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
