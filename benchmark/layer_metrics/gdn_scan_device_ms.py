"""The gated delta rule of the DeltaNet layers (the chunk algebra, the triangular inverse, the scan over chunks that carries the state, and reverse mode through all three), forward, backward and recomputation: self time per step of ops under
the ddlpc/gdn/scan scope (models/olmo_hybrid.py, ops/gated_delta.py)."""

import scope_time


def read(run):
    return scope_time.ms_per_step(run, "ddlpc/gdn/scan")
