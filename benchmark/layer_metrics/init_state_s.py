"""Weights, optimizer state and their placement: the program's t_init_state_s
(host span ddlpc:init/state: create_train_state, op by op, and layout.place)."""

import program_spans


def read(run):
    return program_spans.init_s(run, "state")
