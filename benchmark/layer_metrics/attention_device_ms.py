"""The attention operator (projections, q/k norm, rotary positions, blocked causal attention), forward, backward and recomputation: self time per step of ops under
the ddlpc/attention scope (models/lfm2_moe.py)."""

import scope_time


def read(run):
    return scope_time.ms_per_step(run, "ddlpc/attention")
