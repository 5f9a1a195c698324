"""Where JAX keeps compiled programs between processes.

Entry points (``launch/train.py``, ``launch/serve.py``, ``benchmarks/run.py``
and ``chip_smoke.py``) call ``enable_compile_cache`` once at start-up; the
library never does it at import.  A ``JAX_COMPILATION_CACHE_DIR`` set from
outside wins, and JAX reads it itself.  Otherwise the cache lives in
``.jax_cache/`` of this checkout: a fixed path, because the path is part of
what makes a cache entry hit.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
