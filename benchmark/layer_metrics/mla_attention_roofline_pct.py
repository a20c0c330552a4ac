"""Required FLOPs of latent attention's core (deepseek_v3_flops.attention_flops: q.k at 192 and P.V at 128 over the causal half, forward and backward, recomputation not counted) over the time of the
ddlpc/attention scope (the two-width kernels alone) and the chip's bf16 peak.  Under nn.remat the kernels run the forward twice: 1920 of the 2944 FLOPs a pair and head they do are required, 65 % the ceiling."""

import deepseek_v3_flops
import scope_time


def read(run):
    seq = deepseek_v3_flops.of_run(run)
    ms = scope_time.ms_per_step(run, "ddlpc/attention")
    if not seq or not ms or not run["peak"]:
        return None
    layers = len(seq["model"]["layer_types"])
    required = layers * deepseek_v3_flops.attention_flops(seq["model"], seq["tokens"] / seq["seq_len"], seq["seq_len"])
    return 100.0 * required / (ms / 1e3 * run["peak"]["bf16_flops_per_s"])
