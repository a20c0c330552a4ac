"""JAX's tracing and lowering to StableHLO over construction and warm-up: the
program's compile_trace_s + compile_lower_s (spans ddlpc:compile/trace and
ddlpc:compile/lower), paid on every start, whatever the cache holds."""

import setup_compile


def read(run):
    return setup_compile.total(run, "compile_trace_s", "compile_lower_s")
