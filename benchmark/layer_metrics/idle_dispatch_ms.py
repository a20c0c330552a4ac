"""Device idle per epoch while the host was in ddlpc:epoch_head (set_epoch,
iter), ddlpc:data (the gather's dispatch, or the wait for a host batch) or
ddlpc:step (the compiled step's dispatch)."""

import program_spans


def read(run):
    return program_spans.idle_ms_per_epoch(run, program_spans.GROUPS["dispatch"])
