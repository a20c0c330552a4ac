"""Share of the window's device idle time during which no ddlpc: span was
open on the window's thread: fit()'s preamble and teardown, the loop's own lines."""

import program_spans


def read(run):
    return program_spans.idle_unnamed_pct(run)
