"""Where compiled XLA programs are cached between runs.

JAX's persistent compilation cache is keyed by its directory, so the
directory must not move between runs. ``$JAX_COMPILATION_CACHE_DIR`` wins
when it is set, and this module then sets nothing; otherwise the cache is
``.jax_cache`` at the root of the checkout (git-ignored). Only the entry
points call :func:`enable_compile_cache` — ``chip_smoke.py``,
``python -m repro.launch.train`` and the cluster worker — never
``import repro``, so tests and ahead-of-time compiles stay uncached.
"""

from __future__ import annotations

import os
import sys

#: the cache directory used when ``$JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    The directory is exported in ``$JAX_COMPILATION_CACHE_DIR`` so the
    workers this process launches use the same one."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = DEFAULT_DIR
    if "jax" in sys.modules:
        import jax
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
