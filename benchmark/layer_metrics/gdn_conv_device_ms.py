"""The DeltaNet layers' elementwise side (the depthwise causal taps, SiLU, the L2 norm of q and k, the write strength and the decay, the gated output norm), forward, backward and recomputation: self time per step of ops under
the ddlpc/gdn/conv scope (models/olmo_hybrid.py)."""

import scope_time


def read(run):
    return scope_time.ms_per_step(run, "ddlpc/gdn/conv")
