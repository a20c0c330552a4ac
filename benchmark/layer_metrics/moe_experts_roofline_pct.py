"""Required FLOPs of the expert products (seq_flops.expert_flops of the routed
rows the program counted, forward and backward, recomputation not counted)
over moe_experts_device_ms (the ddlpc/moe/experts scope and the compiler's
ragged-dot kernels) and the chip's bf16 peak: what the grouped product makes
of its rows; compute-bound at a thousand rows an expert and more."""

import scope_time
import seq_flops


def read(run):
    seq = seq_flops.of_run(run)
    ms = scope_time.ms_per_step(run, *scope_time.EXPERT_NEEDLES)
    if not seq or not ms or not run["peak"]:
        return None
    required = 3.0 * seq_flops.expert_flops(seq["model"], seq["rows_routed"])
    return 100.0 * required / (ms / 1e3 * run["peak"]["bf16_flops_per_s"])
