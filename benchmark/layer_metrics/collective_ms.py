"""Summed device durations per step, first chip's plane, of all-reduce,
all-gather, reduce-scatter, collective-permute and all-to-all events."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["steps"]:
        return None
    return trace["collective_s"] / trace["steps"] * 1e3
