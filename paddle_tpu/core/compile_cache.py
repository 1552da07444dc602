"""Where JAX's persistent compilation cache lives.

The path is part of the cache's key, so a directory that moves never
hits: it is placed from outside with ``JAX_COMPILATION_CACHE_DIR`` (JAX
reads that variable itself, and then nothing here touches the config),
or else it is ONE fixed directory inside the checkout, git-ignored —
never a temp name, a pid or a time. Entry points that want the cache
(``chip_smoke.py``, ``bench.py``, ``FLAGS_enable_cinn_compile_cache``)
call :func:`enable_compile_cache`; nothing else sets
``jax_compilation_cache_dir``.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: <checkout>/.jax_compile_cache (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def disable_compile_cache() -> None:
    """Undo :func:`enable_compile_cache`. A cache placed from outside is
    the operator's and stays."""
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", None)
