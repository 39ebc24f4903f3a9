"""Where JAX keeps its persistent compilation cache for this checkout.

Every process that compiles for the chip (a JAX worker lane, the serve and
train drivers, the chip smoke's reference run) calls ``enable_compile_cache``
before its first compile, so a second run on the same machine finds the
programs the first one compiled.

The path is part of the cache's key, so it is fixed: the operator's
``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), otherwise
``<checkout>/.jax_cache``, derived from this module's location — never a
temporary name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
#: src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> Path:
    """Point JAX's persistent cache at its directory for this process and
    return it. With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it
    and nothing is set here."""
    env = os.environ.get(ENV)
    if env:
        return Path(env)
    import jax
    path = CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
