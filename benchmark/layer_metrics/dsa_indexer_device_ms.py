"""The lightning indexer (its three projections of the detached input, rotary positions, the index scores of every causal pair for the selection and again for its loss, and their gradient), forward, backward and recomputation: self time per step of ops under
the ddlpc/dsa/indexer scope (models/keye_vl2.py)."""

import scope_time


def read(run):
    return scope_time.ms_per_step(run, "ddlpc/dsa/indexer")
