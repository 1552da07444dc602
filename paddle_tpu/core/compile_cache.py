"""Where JAX's persistent compilation cache lives.

The path is part of the cache's key, so a directory that moves never
hits: it is placed from outside with ``JAX_COMPILATION_CACHE_DIR`` (JAX
reads that variable itself, and then nothing here touches the config),
or else it is ONE fixed directory inside the checkout, git-ignored —
never a temp name, a pid or a time. Entry points that want the cache
(``benchmark/run.py``, ``chip_smoke.py``,
``FLAGS_enable_cinn_compile_cache``)
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
    """Turn the persistent compilation cache on and return its directory.

    The scopes a program was traced under are part of its key here (JAX
    leaves metadata out by default): an executable cached by a tree with
    other ``jax.named_scope``s, in a directory both share, would load
    with that tree's instruction metadata, and ``profiler/phases.py``
    would read its phases, or none, off this tree's step."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def disable_compile_cache() -> None:
    """Undo :func:`enable_compile_cache`. A cache placed from outside is
    the operator's and stays."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", None)
