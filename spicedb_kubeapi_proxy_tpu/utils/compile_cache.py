"""Persistent XLA compilation cache placement, for entry points only.

The jitted fixpoint programs take seconds to tens of seconds to compile
at the 10M-relationship scale, and every process start would pay them
again. JAX's persistent cache keeps them on disk; its directory is part
of the cache key, so it must not move between runs. Library code never
calls this: a process has one cache, chosen by whoever starts it
(``proxy/cli.py``, the engine host's ``main``, ``proxy/demo.py``,
``chip_smoke.py``, ``benchmark/run.py``).
"""

from __future__ import annotations

import os

# fixed, git-ignored, inside the checkout (the package's parent)
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def place_compile_cache() -> str:
    """Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and
    nothing is set here; otherwise the cache goes to one fixed directory
    inside the checkout. Returns the directory in use."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR
