"""Where entry points keep JAX's persistent compilation cache.

Importing this module changes nothing; an entry point calls
``use_compile_cache`` from its ``main`` before its first compile. Library
code and the tests never turn the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax


def use_compile_cache(checkout) -> str:
    """Point JAX's persistent compilation cache at one directory; return it.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it itself,
    so nothing is set here). Otherwise the cache lives at
    ``<checkout>/.jax_cache``, a fixed path — the path is part of the cache
    key, so a directory that moves never hits — which ``.gitignore`` lists.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(checkout) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
