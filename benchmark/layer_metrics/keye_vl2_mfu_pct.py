"""Required matrix-product FLOPs of a keye_vl2 cell's steps (benchmark/keye_vl2_flops.py: attention over the selected pairs, the indexer over the causal pairs, router, head, experts by the routed-row counter) over the window's wall seconds and the chip's
bf16 peak: the share of the whole step's peak where mfu_pct's conv walk sees nothing."""

import keye_vl2_flops


def read(run):
    seq = keye_vl2_flops.of_run(run)
    if not seq or not run["peak"]:
        return None
    per_step = keye_vl2_flops.step_flops(
        seq["model"], seq["tokens"], seq["pairs_selected"], seq["pairs_causal"], seq["rows_routed"]
    )
    return 100.0 * per_step * seq["steps"] / run["window_s"] / run["peak"]["bf16_flops_per_s"]
