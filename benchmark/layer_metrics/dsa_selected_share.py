"""Share of the causal (query, key) pairs that the indexer picked: the program's dsa_pairs_selected over its dsa_pairs_causal, summed over the window's records (0.2344 at 16,384 positions and topk 2,048; more where scores tie at a threshold)."""


def read(run):
    records = [r for r in run["records"] if "dsa_pairs_selected" in r and "dsa_pairs_causal" in r]
    causal = sum(r["dsa_pairs_causal"] for r in records)
    return sum(r["dsa_pairs_selected"] for r in records) / causal if causal else None
