"""Where JAX keeps its persistent compilation cache.

The cache directory is part of the cache key, so it must not move
between runs: `JAX_COMPILATION_CACHE_DIR` wins when it is set (JAX
reads it itself and nothing here overrides it); otherwise the cache
lives at a fixed path inside the checkout, `<repo>/.jax_cache`
(gitignored). Entry points call `configure()` once before their first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure() -> str:
    """Turn the persistent cache on and return its directory."""
    import jax

    path = os.environ.get(ENV) or DEFAULT_DIR
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
