"""The full-resolution DetailHead, forward and backward: self time per step of
ops whose op_name holds the Flax module name DetailHead."""

import program_spans


def read(run):
    return program_spans.region_ms_per_step(run, "detail_head")
