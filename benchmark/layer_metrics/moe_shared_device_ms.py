"""The always-on shared experts beside the routed ones (one SwiGLU of n_shared x expert width; models/deepseek_v3.py), forward, backward and recomputation: self time per step of ops under the
ddlpc/moe/shared scope."""

import scope_time


def read(run):
    return scope_time.ms_per_step(run, "ddlpc/moe/shared")
