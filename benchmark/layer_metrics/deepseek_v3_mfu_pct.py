"""Required matrix-product FLOPs of a deepseek_v3 cell's steps (benchmark/deepseek_v3_flops.py: MLA's projections and causal core, shared experts, router, dense layer, untied head, routed experts by the routed-row counter) over the window's wall seconds and the chip's
bf16 peak: the share of the whole step's peak where mfu_pct's conv walk sees nothing."""

import deepseek_v3_flops


def read(run):
    seq = deepseek_v3_flops.of_run(run)
    if not seq or not run["peak"]:
        return None
    per_step = deepseek_v3_flops.step_flops(seq["model"], seq["tokens"], seq["seq_len"], seq["rows_routed"])
    return 100.0 * per_step * seq["steps"] / run["window_s"] / run["peak"]["bf16_flops_per_s"]
