"""JAX's persistent compilation cache, placed by the entry points.

``chip_smoke.py``, ``launch/serve.py`` and ``benchmarks/run.py`` call
``enable_compile_cache()`` before their first compile; importing the
library never touches the cache. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it and the cache lives there: nothing else is set in code.
Otherwise the cache lives at the fixed path ``<checkout>/.jax_cache``
(gitignored). The path is fixed, never built from a temp name, a pid or
the time, because a later run finds its entries only at the same path.

This module imports JAX only inside ``enable_compile_cache``, so a parent
process that starts device-owning children can read the path
(``compile_cache_dir``) without touching JAX.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The directory the cache lives in: the variable's, else the fixed
    checkout path."""
    return os.environ.get(ENV) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return the directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
