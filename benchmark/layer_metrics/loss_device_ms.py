"""The loss, forward and backward: self time per step of ops under the
ddlpc/loss scope (loss_from_logits)."""

import program_spans


def read(run):
    return program_spans.region_ms_per_step(run, "loss")
