"""The dense SwiGLU feed-forward (three projections and the gate between them), forward, backward and recomputation: self time per step of ops under
the ddlpc/dense_ffn scope (models/lfm2_moe.py:SwiGLU, as models/olmo_hybrid.py runs it in every layer)."""

import scope_time


def read(run):
    return scope_time.ms_per_step(run, "ddlpc/dense_ffn")
