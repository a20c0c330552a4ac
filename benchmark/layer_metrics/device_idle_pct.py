"""1 - union of busy intervals / traced window, first chip's plane."""


def read(run):
    trace = run["trace"]
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"][min(trace["busy_s"])] / trace["window_s"])
