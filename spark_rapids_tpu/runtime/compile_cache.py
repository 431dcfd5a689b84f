"""Where JAX's persistent compilation cache lives.

One rule, shared by chip_smoke.py, benchmark/run.py, tests/conftest.py and
__graft_entry__.py: the environment decides, and otherwise the path is fixed.
The path is part of the cache key, so a directory that moves never hits.

The engine's own compiled-stage cache (runtime/stage_cache.py) is a separate,
opt-in store; when configured it switches this one off.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, no directory is
    set in code. Not set: ``<checkout>/.jax_cache``. Failures raise."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return path
