"""Where the persistent XLA compile cache lives.

Every chip call starts from nothing unless compiled programs persist on
disk, and the cache directory is part of the cache key — a directory that
moves between runs never hits. So the entry points (`dnn_tpu.node`,
`chip_smoke.py`, `chipbench/pipe.py`) call
`enable_compile_cache()` once, before their first compile:

  * `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself; nothing is
    configured in code, so the operator's directory is the only one;
  * unset: `<checkout>/.jax_cache` — fixed by the package's location,
    identical in every process of one checkout (the daemon child and the
    script that spawned it share entries).

Never called at `import dnn_tpu`: a library import must not start
writing to disk. Distinct from `utils/xla_cache.CompileCacheGuard`, which
bounds the IN-MEMORY executable caches of a long-lived process.
"""

from __future__ import annotations

import os

__all__ = ["compile_cache_dir", "enable_compile_cache"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The directory this process's persistent compile cache uses once
    `enable_compile_cache()` has run."""
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at `compile_cache_dir()`
    (see module docstring) and return that directory."""
    path = compile_cache_dir()
    if not os.environ.get(_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
