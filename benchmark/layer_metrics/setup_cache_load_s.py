"""Executables loaded from the persistent cache over construction and
warm-up: the program's compile_load_s (spans ddlpc:compile/backend that saw a
cache hit)."""

import setup_compile


def read(run):
    return setup_compile.total(run, "compile_load_s")
