"""Tiles and the loader's device cache: the program's t_init_dataset_s +
t_init_loader_s (host spans ddlpc:init/dataset and ddlpc:init/loader of
Trainer.__init__) from the first record of the Trainer's life."""

import program_spans


def read(run):
    return program_spans.init_s(run, "dataset", "loader")
