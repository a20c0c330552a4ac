"""Programs XLA compiled over construction and warm-up: the program's
programs_compiled.  On a warm start, more than the programs under the cache's
thresholds means an entry was missing (never written, or evicted)."""

import setup_compile


def read(run):
    return setup_compile.total(run, "programs_compiled")
