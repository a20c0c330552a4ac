"""Required FLOPs of the indexers (keye_vl2_flops.indexer_flops: the three projections and the scores of every causal pair, forward and backward, recomputation not counted) over the time of the
ddlpc/dsa/indexer scope and the chip's bf16 peak: the index scores are float32 products written to HBM and read back a head at a time, so the share is low until a kernel keeps them on the chip."""

import keye_vl2_flops
import scope_time


def read(run):
    seq = keye_vl2_flops.of_run(run)
    ms = scope_time.ms_per_step(run, "ddlpc/dsa/indexer")
    if not seq or not ms or not run["peak"]:
        return None
    layers = len(seq["model"]["layer_types"])
    required = keye_vl2_flops.indexer_flops(seq["model"], layers * seq["tokens"], seq["pairs_causal"])
    return 100.0 * required / (ms / 1e3 * run["peak"]["bf16_flops_per_s"])
