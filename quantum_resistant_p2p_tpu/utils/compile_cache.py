"""JAX's persistent compilation cache, turned on the same way by every entry
point (the CLI, ``chip_smoke.py``, ``bench.py``, ``tools/*`` and the tests).

The crypto programs are compile-heavy (a minute or more for each fused
handshake program that ends in ML-DSA signing).  The cache saves the XLA
compile; tracing and lowering the Pallas kernels are paid on every start.

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX
reads it itself, so nothing is set here), otherwise :data:`DEFAULT_DIR`, a
fixed directory inside the checkout.  What gets cached is JAX's default
policy (programs that took a second or more to compile).  The path is part of what makes a
cache hit, so it is never built from a temp name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the fallback cache directory: fixed, inside the checkout, git-ignored
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first jit; returns its path."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
