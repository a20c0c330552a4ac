"""Host clock round Trainer(...): tiles, weights, mesh, loader, step builders."""


def read(run):
    return run["phase_seconds"]["trainer_init"]
