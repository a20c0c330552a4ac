"""Median over the traced epochs of epoch wall minus device-busy time inside
it (both from the trace): what the Trainer loop adds to the device's work."""

import statistics


def read(run):
    trace = run["trace"]
    if not trace or not trace["epoch_overhead_s"]:
        return None
    return statistics.median(trace["epoch_overhead_s"]) * 1e3
