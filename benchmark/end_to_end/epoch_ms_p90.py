"""90th percentile of the window's epoch times (the benchmark's own clock
round each train_epoch call, which ends in the epoch's device_get).  Only
where an epoch is one optimizer step and the window holds at least 80."""

import statistics

MIN_EPOCHS = 80


def read(run):
    if run["steps_per_epoch"] != 1 or len(run["records"]) < MIN_EPOCHS:
        return None
    return statistics.quantiles([r["wall_s"] for r in run["records"]], n=10)[-1] * 1e3
