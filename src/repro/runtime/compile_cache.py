"""Where JAX keeps its persistent compilation cache.

Entry points (``launch.serve``, ``launch.train``, ``chip_smoke.py``) call
``use_compile_cache()`` once at start-up; importing this module changes
nothing. A cache directory that moves never hits, so the default is one
fixed directory inside the checkout, never a temporary, per-process or
per-run name.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_compile_cache (listed in .gitignore)
DEFAULT_DIR = str(Path(__file__).resolve().parents[3] / ".jax_compile_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already reads it and it is left as it is; otherwise the cache goes
    to ``DEFAULT_DIR``."""
    where = os.environ.get(ENV)
    if where:
        return where
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
