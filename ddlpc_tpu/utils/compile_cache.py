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

The module also keeps the process's compile ledger (:class:`CompileLedger`):
what JAX traced, lowered, compiled or loaded from this cache, as spans on the
profiler's clock and as counters the Trainer's records carry.  It is the one
place in the program that listens to JAX's compile events.
"""

from __future__ import annotations

import collections
import os
import re
import threading

from ddlpc_tpu.analysis import lockcheck

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory, install
    the compile ledger, and return that directory.  Call first thing in
    ``main()``."""
    import jax

    install_compile_ledger()
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


# The three phases jax/_src/dispatch.py:log_elapsed_time wraps: each opens with
# record_scalar(event, start_time, fun_name=...) and closes with
# record_event_duration_secs(event, secs, fun_name=...) on the same thread.
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
# Fired inside a backend phase that the persistent cache served.  Not
# ``cache_misses``: that one means an entry was written, and a program under
# the cache's thresholds is compiled without one.
CACHE_HIT = "/jax/compilation_cache/cache_hits"
PHASES = {TRACE: "trace", LOWER: "lower", BACKEND: "backend"}
SPAN_PREFIX = "ddlpc:compile/"
COUNTERS = (
    "compile_trace_s",
    "compile_lower_s",
    "compile_load_s",
    "compile_xla_s",
    "programs_loaded",
    "programs_compiled",
)
NAMES_KEPT = 8  # programs_compiled_names per delta


def _zeros() -> dict:
    return {k: 0.0 if k.endswith("_s") else 0 for k in COUNTERS}


@lockcheck.guarded
class CompileLedger:
    """Every trace, lowering and compile-or-cache-load of this process.

    Each phase is a ``jax.profiler.TraceAnnotation`` named
    ``ddlpc:compile/<trace|lower|backend>`` with argument ``fun``, opened by
    the phase's start event and closed by its duration event, so it nests
    under whatever ``ddlpc:`` stage caused the compile (docs/OBSERVABILITY.md).
    Its seconds go to one counter, and only where no other phase is open
    beneath it on the thread: the trace of ``f`` encloses the traces of the
    jitted functions ``f`` calls.  A backend phase that saw :data:`CACHE_HIT`
    is a load (``compile_load_s``, ``programs_loaded``), any other an XLA
    compile (``compile_xla_s``, ``programs_compiled``).  A call that JAX's
    in-memory caches serve fires no event: the ledger costs nothing there.

    Installed once per process (:func:`install_compile_ledger`); readers take
    deltas through a :meth:`cursor`."""

    def __init__(self):
        self._lock = lockcheck.lock("CompileLedger._lock")
        self._totals = _zeros()  # guarded-by: _lock
        # (programs_compiled counting it, fun_name) of the latest compiles
        self._names = collections.deque(maxlen=64)  # guarded-by: _lock
        self._local = threading.local()
        self._installed = False  # guarded-by: _lock

    def install(self) -> "CompileLedger":
        import jax

        with self._lock:
            if not self._installed:
                jax.monitoring.register_scalar_listener(self._open)
                jax.monitoring.register_event_listener(self._hit)
                jax.monitoring.register_event_duration_secs_listener(self._close)
                self._installed = True
        return self

    def _stack(self) -> list:
        """``[event, annotation, cache hit]`` of the phases open on this thread."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _open(self, event: str, value, fun_name: str = "", **_) -> None:
        phase = PHASES.get(event)
        if phase is None:
            return
        import jax

        span = jax.profiler.TraceAnnotation(SPAN_PREFIX + phase, fun=fun_name)
        span.__enter__()
        self._stack().append([event, span, False])

    def _hit(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            stack = self._stack()
            if stack and stack[-1][0] == BACKEND:
                stack[-1][2] = True

    def _close(self, event: str, secs: float, fun_name: str = "", **_) -> None:
        if event not in PHASES:
            return
        stack = self._stack()
        if not stack or stack[-1][0] != event:
            return
        _, span, hit = stack.pop()
        span.__exit__(None, None, None)
        with self._lock:
            totals = self._totals
            if event == BACKEND:
                totals["programs_loaded" if hit else "programs_compiled"] += 1
                if not hit:
                    self._names.append((totals["programs_compiled"], fun_name))
            if not stack:
                key = {TRACE: "compile_trace_s", LOWER: "compile_lower_s"}.get(event)
                totals[key or ("compile_load_s" if hit else "compile_xla_s")] += secs

    def _since(self, mark: dict) -> tuple:
        """``(what changed since mark, the totals now)``, under one lock."""
        with self._lock:
            now = dict(self._totals)
            names = [n for i, n in self._names if i > mark["programs_compiled"]]
        delta = {k: now[k] - mark[k] for k in COUNTERS}
        if names:
            delta["programs_compiled_names"] = names[:NAMES_KEPT]
        return delta, now

    def cursor(self) -> "LedgerCursor":
        return LedgerCursor(self)


class LedgerCursor:
    """One reader's place in the ledger: :meth:`take` returns the counters'
    growth since the cursor was made or last taken, and
    ``programs_compiled_names`` (at most :data:`NAMES_KEPT`) where any
    program was compiled meanwhile."""

    def __init__(self, ledger: CompileLedger):
        self._ledger = ledger
        _, self._mark = ledger._since(_zeros())

    def take(self) -> dict:
        delta, self._mark = self._ledger._since(self._mark)
        return delta


LEDGER = CompileLedger()


def install_compile_ledger() -> CompileLedger:
    """The process's one ledger, its listeners registered (idempotent)."""
    return LEDGER.install()
