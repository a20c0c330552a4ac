"""Required FLOPs of the attention operators (seq_flops.attention_flops: the
q, k, v and o projections and the causal half of the scores, forward and
backward, recomputation not counted) over the time of the ddlpc/attention
scope and the chip's bf16 peak."""

import scope_time
import seq_flops


def read(run):
    seq = seq_flops.of_run(run)
    ms = scope_time.ms_per_step(run, "ddlpc/attention")
    if not seq or not ms or not run["peak"]:
        return None
    required = seq_flops.attention_flops(seq["model"], seq["seq_len"], seq["sequences"])
    return 100.0 * required / (ms / 1e3 * run["peak"]["bf16_flops_per_s"])
