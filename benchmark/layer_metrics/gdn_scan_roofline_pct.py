"""Required FLOPs of the delta-rule scans (olmo_hybrid_flops.scan_flops: the chunk-64 products a token and held head, forward and backward; the triangular inverse and recomputation not counted) over the time of the
ddlpc/gdn/scan scope and the chip's bf16 peak: 128 sequential chunk steps a layer of small products at head sizes 96 and 192, so the share is low while latency, not the MXU, bounds the scan."""

import olmo_hybrid_flops
import scope_time


def read(run):
    seq = olmo_hybrid_flops.of_run(run)
    ms = scope_time.ms_per_step(run, "ddlpc/gdn/scan")
    if not seq or not ms or not run["peak"]:
        return None
    linear = seq["model"]["layer_types"].count("linear_attention")
    required = olmo_hybrid_flops.scan_flops(seq["model"], linear * seq["tokens"])
    return 100.0 * required / (ms / 1e3 * run["peak"]["bf16_flops_per_s"])
