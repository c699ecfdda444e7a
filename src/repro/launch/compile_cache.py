"""JAX's persistent compilation cache, placed by the entry points.

``chip_smoke.py``, ``launch/serve.py`` and ``benchmarks/run.py`` call
``enable_compile_cache()`` before their first compile; importing the
library never touches the cache. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it and the cache lives there: nothing else is set in code.
Otherwise the cache lives at the fixed path ``<checkout>/.jax_cache``
(gitignored). The path is fixed, never built from a temp name, a pid or
the time, because a later run finds its entries only at the same path.

The cache key includes the program's metadata (each operation's
``op_name``, with its ``jax.named_scope`` stage names, and its source
location). JAX leaves metadata out of the key by default, and a
program whose stages were named or renamed since an entry was stored
would then load that entry and run under the old names: a profiler
trace would attribute its device time to stages the program no longer
has. Source files are named relative to the checkout, so the key does
not depend on where the checkout lies; an edit that moves a traced
line compiles the program again, as an edit to its operations does.

This module imports JAX only inside ``enable_compile_cache``, so a parent
process that starts device-owning children can read the path
(``compile_cache_dir``) without touching JAX.
"""

from __future__ import annotations

import os
import re
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
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(CHECKOUT) + os.sep))
    return path
