"""JAX's persistent compilation cache, at one fixed place per checkout."""
from __future__ import annotations

import os
from pathlib import Path

# fixed, in the checkout (and git-ignored): the directory is part of what
# lets a later process find an entry, so it never depends on a temp dir,
# a pid or the time
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on before the first compile
    and return its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already reads it and nothing here overrides it; otherwise the
    cache goes to ``DEFAULT_DIR``."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
