"""Required matrix-product FLOPs of a sequence model's steps
(benchmark/seq_flops.py: projections, experts by the routed-row counter,
attention, head) over the window's wall seconds and the chip's bf16 peak: the
share of the whole step's peak where mfu_pct's conv walk sees the 1x1
projections only."""

import seq_flops


def read(run):
    seq = seq_flops.of_run(run)
    if not seq or not run["peak"]:
        return None
    per_step = seq_flops.step_flops(seq["model"], seq["seq_len"], seq["sequences"], seq["rows_routed"])
    achieved = per_step * seq["steps"] / run["window_s"]
    return 100.0 * achieved / run["peak"]["bf16_flops_per_s"]
