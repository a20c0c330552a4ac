"""Required conv FLOPs (benchmark/flops.py) of the window's steps over the
window's wall seconds and the chip's bf16 peak (benchmark/peaks.json)."""


def read(run):
    if not run["peak"]:
        return None
    steps = len(run["records"]) * run["steps_per_epoch"]
    achieved = run["flops_per_step_per_chip"] * steps / run["window_s"]
    return 100.0 * achieved / run["peak"]["bf16_flops_per_s"]
