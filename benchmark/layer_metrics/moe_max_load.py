"""Routing imbalance: the largest held expert's rows over the mean of the held
experts, the worst routed layer and micro-batch of each epoch (the program's
moe_max_load counter), median over the window's records."""

import statistics


def read(run):
    loads = [r["moe_max_load"] for r in run["records"] if "moe_max_load" in r]
    return statistics.median(loads) if loads else None
