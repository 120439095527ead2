"""Where JAX's persistent compilation cache lives.

One rule, applied by every entry point before its first compile
(``FiloServer.start``, ``cli serve``, ``chip_smoke.py``): if
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is set
in code; otherwise the cache is ``<checkout>/.jax_cache`` — a fixed,
git-ignored path, because the path is part of the cache key and a directory
that moves (a temp name, a pid, a time) never hits.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def configure() -> str:
    """Place the compile cache (idempotent); returns the directory in use."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed               # JAX's own: set nothing in code
    import jax
    if jax.config.jax_compilation_cache_dir != DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
