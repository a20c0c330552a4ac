"""Where the persistent XLA compilation cache lives — one decision, made by
every process entry point before its first compile.

A trainer start, a supervised restart and a serve replica all compile the
same programs; without a shared cache each pays the full compile.  The
cache key includes the directory, so the path must not move between
processes: it is either what ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads
the variable itself — nothing here overrides it) or the fixed
``<checkout>/.jax_cache`` derived from the package location.

The key includes each instruction's metadata.  JAX's default strips it, so a
program whose ``jax.named_scope``s changed and whose arithmetic did not would
load the OLD executable, and every profile of it (a SIGUSR2 capture, the
benchmark's traced run) would show the old ``op_name``s — the names the
per-region device metrics read (docs/OBSERVABILITY.md).  Metadata holds
source paths, so the checkout's own prefix is taken out of them: the key
does not move with the checkout, only with the code.  It does move with the
Python call stack a program is traced under (JAX puts up to ten frames into
each location), so each entry point caches its own copy of a program.
Dropping the frames (``jax_include_full_tracebacks_in_locations=False``)
is not an option on jax 0.9.0: it also drops the enclosing name stack of
every op inside a ``shard_map`` body, scopes included (measured, PR 25).
"""

from __future__ import annotations

import os
import re

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory.  Call first thing in ``main()``."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex",
        re.escape(CHECKOUT + os.sep),
    )
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
