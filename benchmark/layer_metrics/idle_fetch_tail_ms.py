"""Device idle per epoch while the host was in ddlpc:metrics_fetch (the
epoch's one device_get: its wake-up after the last op) or ddlpc:epoch_tail (float
conversion, the record, goodput bookkeeping)."""

import program_spans


def read(run):
    return program_spans.idle_ms_per_epoch(run, program_spans.GROUPS["fetch_tail"])
