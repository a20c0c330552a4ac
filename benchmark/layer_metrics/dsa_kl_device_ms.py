"""The indexer's own loss (the head-averaged attention probabilities recomputed from detached q and k and the attention's log-sum-exp, the KL over the picked keys against the softmax of the index scores, and its gradient as far as those scores), forward and backward: self time per step of ops under
the ddlpc/dsa/kl scope (models/keye_vl2.py)."""

import scope_time


def read(run):
    return scope_time.ms_per_step(run, "ddlpc/dsa/kl")
