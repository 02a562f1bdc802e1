"""Where JAX's persistent compilation cache lives: the one definition.

``chip_smoke.py``, ``bench.py``'s leg processes and ``tests/conftest.py``
all call ``enable_persistent_cache``. The cache's path is part of its
key, so a directory that moves never hits:

- if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  module sets nothing;
- otherwise the cache is ``<checkout>/.jax_cache/``, the same path on
  every run (gitignored).

Entries key on the HLO hash and the target inside the directory, so
source changes and other backends miss naturally.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def cache_dir() -> str:
    return os.environ.get(ENV_VAR) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_persistent_cache() -> None:
    """Point JAX at ``cache_dir()`` unless the environment already does."""
    if os.environ.get(ENV_VAR):
        return
    import jax

    os.makedirs(cache_dir(), exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir())
