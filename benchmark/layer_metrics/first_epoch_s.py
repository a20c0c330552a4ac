"""The program's epoch_time_s of warm-up epoch 0: compilation, or the load
from the persistent cache, plus one epoch."""


def read(run):
    return run["warmup_records"][0]["epoch_time_s"]
