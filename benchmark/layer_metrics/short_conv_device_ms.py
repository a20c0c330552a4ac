"""The gated short-convolution operators (in and out projections, gates, depthwise causal taps), forward, backward and recomputation: self time per step of ops under
the ddlpc/short_conv scope (models/lfm2_moe.py)."""

import scope_time


def read(run):
    return scope_time.ms_per_step(run, "ddlpc/short_conv")
