"""Where entry points keep JAX's persistent compilation cache.

Called by the entry points (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``examples/serve_ternary.py``,
``benchmarks.run``), never on library import.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
else is set here.  Otherwise the cache goes to a fixed directory inside
the checkout (``<repo>/.jax_cache``, git-ignored): the directory is
part of every entry's key, so a path that moved between runs would
never hit.
"""
from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
