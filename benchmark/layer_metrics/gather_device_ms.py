"""The device cache's gather program (one run per step: take, reshape and
reshard the super-batch): self time per step of ops under the ddlpc/gather
scope, or without an op_name inside a run of jit_gather."""

import program_spans


def read(run):
    return program_spans.region_ms_per_step(run, "gather")
