"""The backward pass outside the loss and the DetailHead: self time per step of
ops whose op_name holds transpose( and no earlier region of regions.json."""

import program_spans


def read(run):
    return program_spans.region_ms_per_step(run, "backward")
