"""The persistent XLA compile cache of the entry points.

Called by ``launch/train.py``, ``launch/serve.py`` and ``chip_smoke.py``
before their first compile — never at library import, so tests and
library users keep JAX's own configuration.
"""
from __future__ import annotations

import os

from repro import CHECKOUT_DIR

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Keep compiled programs across processes and return the cache
    directory. Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and this changes nothing; otherwise the cache goes to
    ``<checkout>/.jax_cache``, a fixed path, since the path is part of
    what a cached entry is found by."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax

    path = os.path.join(CHECKOUT_DIR, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
