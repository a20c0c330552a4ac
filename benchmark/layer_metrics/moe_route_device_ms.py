"""Routing (router scores, top-k, the sort into expert groups, the gathers there and back, the weighted sum), forward, backward and recomputation: self time per step of ops under
the ddlpc/moe/route scope (models/lfm2_moe.py)."""

import scope_time


def read(run):
    return scope_time.ms_per_step(run, "ddlpc/moe/route")
