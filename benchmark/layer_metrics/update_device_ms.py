"""Adam on the (sharded) state, the chunking before it and the params
all-gather after it: self time per step of ops under ddlpc/update."""

import program_spans


def read(run):
    return program_spans.region_ms_per_step(run, "update")
