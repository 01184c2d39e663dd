"""JAX's persistent compilation cache, at one fixed place per checkout.

A cold serving boot at full width compiles every prefill bucket and decode
chunk of a 28-layer model, and each supervisor generation or relaunch would
compile them all again. The persistent cache keeps the compiled programs on
disk; its key includes the directory, so the directory must not move
between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set → JAX already reads it; nothing else is
  set here.
* unset → ``<checkout>/.jax_cache`` (listed in ``.gitignore``), never a name
  built from a temporary directory, a pid or the time.

Call :func:`enable_compile_cache` at the top of an entry point, before the
first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
