"""Process start to the first measured epoch: imports, devices, tiles,
weights, Trainer(...), warm-up (compilation or cache load), reference check."""


def read(run):
    return run["setup_seconds"]
