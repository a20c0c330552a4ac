"""Codec encode, collectives, decode, the gradient norm and the batch-stat and
metric pmeans: self time per step of ops under ddlpc/grad_sync."""

import program_spans


def read(run):
    return program_spans.region_ms_per_step(run, "grad_sync")
