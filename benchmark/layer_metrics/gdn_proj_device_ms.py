"""The DeltaNet layers' projections (q, k, v, the gate, the two scalar gates a head, and W_o), forward, backward and recomputation: self time per step of ops under
the ddlpc/gdn/proj scope (models/olmo_hybrid.py)."""

import scope_time


def read(run):
    return scope_time.ms_per_step(run, "ddlpc/gdn/proj")
