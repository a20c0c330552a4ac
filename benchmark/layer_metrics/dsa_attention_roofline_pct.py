"""Required FLOPs of the attention operators (keye_vl2_flops.attention_flops: the q, k, v and o projections and the products over the SELECTED pairs the program counted, forward and backward, recomputation not counted) over the time of the
ddlpc/attention scope and the chip's bf16 peak: low while the kernels compute every causal block and mask."""

import keye_vl2_flops
import scope_time


def read(run):
    seq = keye_vl2_flops.of_run(run)
    ms = scope_time.ms_per_step(run, "ddlpc/attention")
    if not seq or not ms or not run["peak"]:
        return None
    layers = len(seq["model"]["layer_types"])
    required = keye_vl2_flops.attention_flops(seq["model"], layers * seq["tokens"], seq["pairs_selected"])
    return 100.0 * required / (ms / 1e3 * run["peak"]["bf16_flops_per_s"])
