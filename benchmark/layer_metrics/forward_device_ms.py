"""The forward pass outside the loss and the DetailHead: self time per step of
ops under ddlpc/accumulate that no earlier region of regions.json takes."""

import program_spans


def read(run):
    return program_spans.region_ms_per_step(run, "forward")
