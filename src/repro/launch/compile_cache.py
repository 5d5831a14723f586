"""Where JAX's persistent compilation cache lives for this checkout.

Called from the mains (``launch/serve.py``, ``launch/train.py``,
``launch/tune.py``) and ``chip_smoke.py`` before their first compile, never
when ``repro`` is imported.  A path is part of the cache's key, so the cache
sits at one fixed place: the directory ``JAX_COMPILATION_CACHE_DIR`` names
(JAX reads it itself), else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
