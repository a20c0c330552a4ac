"""The selection (each query's threshold, the topk-th largest of its index scores by bisection on the float's bits; the [S, S] bias the attention adds; the count of picked pairs), forward and recomputation: self time per step of ops under
the ddlpc/dsa/select scope (models/keye_vl2.py)."""

import scope_time


def read(run):
    return scope_time.ms_per_step(run, "ddlpc/dsa/select")
