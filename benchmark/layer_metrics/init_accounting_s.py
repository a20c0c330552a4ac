"""The FLOP model, HBM gauges, comm plan and comm probe: the program's
t_init_accounting_s (host span ddlpc:init/accounting)."""

import program_spans


def read(run):
    return program_spans.init_s(run, "accounting")
