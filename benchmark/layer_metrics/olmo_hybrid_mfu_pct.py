"""Required matrix-product FLOPs of an olmo_hybrid cell's steps (benchmark/olmo_hybrid_flops.py: the DeltaNet projections and scans, attention's projections and causal half, SwiGLU and the untied head over the heads and columns held) over the window's wall seconds and the chip's
bf16 peak: the share of the whole step's peak where mfu_pct's conv walk sees nothing."""

import olmo_hybrid_flops


def read(run):
    seq = olmo_hybrid_flops.of_run(run)
    if not seq or not run["peak"]:
        return None
    per_step = olmo_hybrid_flops.step_flops(seq["model"], seq["tokens"], seq["seq_len"])
    return 100.0 * per_step * seq["steps"] / run["window_s"] / run["peak"]["bf16_flops_per_s"]
