"""Where JAX's persistent compilation cache lives.

One rule for every launcher (``bench.py``, ``chip_smoke.py``, the CLI
in ``application.py``): the cache is placed from outside. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as the
default of ``jax_compilation_cache_dir`` and this module sets nothing;
otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored). The
directory is part of the cache key, so it is a fixed path — never a
temp name, pid or time. The library itself enables no cache: importing
``lightgbm_tpu`` changes no JAX configuration.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Apply the rule above; returns the directory now in force."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir
