"""Mean host wait for the next super-batch per loader call, from the
program's own records (t_data_s)."""

import statistics


def read(run):
    waits = [r["t_data_s"] for r in run["records"] if "t_data_s" in r]
    return statistics.fmean(waits) * 1e3 if waits else None
