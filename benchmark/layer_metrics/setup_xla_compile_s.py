"""XLA compiles over construction and warm-up: the program's compile_xla_s
(spans ddlpc:compile/backend that the persistent cache did not serve)."""

import setup_compile


def read(run):
    return setup_compile.total(run, "compile_xla_s")
