"""The grouped expert products (three a routed row, and the gate between them),
forward, backward and recomputation: self time per step of ops under the
ddlpc/moe/experts scope (models/lfm2_moe.py) and of the compiler's ragged-dot
kernels (scope_time.EXPERT_NEEDLES)."""

import scope_time


def read(run):
    return scope_time.ms_per_step(run, *scope_time.EXPERT_NEEDLES)
