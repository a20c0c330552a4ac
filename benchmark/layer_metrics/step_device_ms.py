"""Device-busy time per optimizer step on the first chip's plane."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["steps"]:
        return None
    return trace["busy_s"][min(trace["busy_s"])] / trace["steps"] * 1e3
