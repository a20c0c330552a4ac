"""Where the persistent XLA compilation cache lives — one decision, made by
every process entry point before its first compile.

A trainer start, a supervised restart and a serve replica all compile the
same programs; without a shared cache each pays the full compile.  The
cache key includes the directory, so the path must not move between
processes: it is either what ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads
the variable itself — nothing here overrides it) or the fixed
``<checkout>/.jax_cache`` derived from the package location.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory.  Call first thing in ``main()``."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
