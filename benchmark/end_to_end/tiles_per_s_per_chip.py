"""Tiles trained in the window / wall seconds of the window's fit() / chips.
Wrap-around tiles count as trained: they are real forward and backward work."""


def read(run):
    tiles = len(run["records"]) * run["steps_per_epoch"] * run["tiles_per_step"]
    return tiles / run["window_s"] / run["chips"]
