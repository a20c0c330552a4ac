"""Share of the window's device self time in no region of regions.json: the
step's entry cast and parameter copies (XLA leaves them without an op_name, and
no named op encloses them), and whatever a later change forgets to scope."""

import program_spans


def read(run):
    return program_spans.region_pct(run, program_spans.UNNAMED)
