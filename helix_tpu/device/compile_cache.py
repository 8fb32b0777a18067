"""The one rule for JAX's persistent compilation cache.

Every program of this repository that compiles for the accelerator
(``serve-node``, ``chip_smoke.py``'s children) calls
:func:`configure_compile_cache` before its first compile, and nothing
else sets a cache directory:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; do nothing.
- otherwise: ``<checkout>/.jax_cache`` (git-ignored).  A fixed path — the
  path is part of the cache key, so a temporary name, a pid or a time
  would never hit.

A CPU backend keeps no persistent cache at all, in tests
(``tests/conftest.py``) as in a dev ``serve-node``: XLA:CPU's cached
executables reload with machine-feature warnings and save seconds, where
the chip's save minutes.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def configure_compile_cache() -> str:
    """Apply the rule; returns the directory in force ("" = none)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    if jax.devices()[0].platform == "cpu":
        return ""
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
