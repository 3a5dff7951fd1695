"""Where XLA's persistent compilation cache lives.

A process that owns a chip calls `ensure_compile_cache()` before its
first compile (TPU workers in `worker_main`, `chip_smoke.py`). The
directory is part of the cache key's
lookup, so it must not move between runs:

* `JAX_COMPILATION_CACHE_DIR` set — JAX reads it itself; nothing here
  touches it and no code in the repo names another directory.
* unset — `<checkout>/.jax_cache`, derived from the package location
  (never a temp dir, pid or timestamp), exported so every child that
  inherits the environment (`daemon._worker_env` copies `os.environ`)
  uses the same path.

Nothing here imports jax: a driver that must stay off the chip can
call this to place the cache for its workers.
"""

from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def ensure_compile_cache() -> str:
    """Place the cache (see module docstring); returns its path."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    os.environ[ENV_VAR] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax read the (then unset) variable when it was imported.
        jax.config.update("jax_compilation_cache_dir", path)
    return path
