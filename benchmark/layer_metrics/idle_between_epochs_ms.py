"""Device idle per epoch under every other ddlpc: span: fit()'s ddlpc:log
(logger.log + health) and ddlpc:perf_publish between two epochs, ddlpc:epoch's
own time, and evaluate / checkpoint / dump_images where the mix has them."""

import program_spans


def read(run):
    return program_spans.idle_ms_per_epoch(run, None)
