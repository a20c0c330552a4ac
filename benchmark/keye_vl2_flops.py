"""Required matrix-product FLOPs of one optimizer step of the ``keye_vl2``
family, from the configuration's shapes and the program's counters
(``dsa_pairs_selected``, ``dsa_pairs_causal``, ``moe_rows_routed``):
``seq_flops.py`` takes the head size as ``hidden / heads`` and a tied head, so
it cannot serve.  Kept with the benchmark so that no later PR moves the
numerators of the ``dsa_*_roofline_pct`` shares or of ``keye_vl2_mfu_pct``.

Counted per step, forward and backward (three times the forward's products
but where said), recomputation not counted:

  attention  the q, k, v and o projections, and 4 · head size · query heads a
             SELECTED pair (q·k and p·v over the keys the indexer picked:
             whatever computes them, so a kernel that skips unpicked blocks
             is read on the same work and the share cannot pass 100 %)
  indexer    its three projections at twice the forward (their input is
             detached: forward and weight gradient) and 2 · heads · head size
             a causal pair (every earlier key is scored)
  the step   those, the router, the untied head, and the experts' three
             products a routed row

Not counted: the second q·kᵀ pass that makes the indexer's target (the
attention computed those probabilities already), norms, rotary, softmax, the
threshold, routing, loss and Adam.
"""

from __future__ import annotations

import seq_flops


def _sizes(model: dict):
    heads = model["num_attention_heads"]
    head_dim = model.get("head_dim") or model["hidden_size"] // heads
    return model["hidden_size"], heads, model["num_key_value_heads"], head_dim


def attention_flops(model: dict, tokens: float, pairs_selected: float) -> float:
    """Forward and backward FLOPs of the attention operators over ``tokens``
    positions and ``pairs_selected`` (query, picked key) pairs, both summed
    over the layers."""
    d, heads, kv, head_dim = _sizes(model)
    projections = 2 * d * heads * head_dim * 2 + 2 * d * kv * head_dim * 2  # q and o, k and v
    return 3.0 * (tokens * projections + 4 * head_dim * heads * pairs_selected)


def indexer_flops(model: dict, tokens: float, pairs_causal: float) -> float:
    """Forward and backward FLOPs of the indexers over ``tokens`` positions
    and ``pairs_causal`` (query, earlier key) pairs, both summed over the layers."""
    j, dim = model["indexer_num_heads"], model["indexer_head_dim"]
    projections = 2 * model["hidden_size"] * (j * dim + dim + j)
    return 2.0 * tokens * projections + 3.0 * 2 * j * dim * pairs_causal


def step_flops(model: dict, tokens: float, pairs_selected: float, pairs_causal: float,
               rows_routed: float) -> float:
    """``tokens`` is positions a step (one layer's worth)."""
    d = model["hidden_size"]
    layers = len(model["layer_types"])
    other = tokens * (layers * 2 * d * model["num_experts"] + 2 * d * model["num_classes"])
    other += seq_flops.expert_flops(model, rows_routed)
    return (
        attention_flops(model, layers * tokens, pairs_selected)
        + indexer_flops(model, layers * tokens, pairs_causal)
        + 3.0 * other
    )


def of_run(run: dict) -> dict | None:
    """``{"model", "tokens", "pairs_selected", "pairs_causal", "rows_routed",
    "steps"}`` of a run of a ``keye_vl2`` cell, the counters per step and
    chip; None for any other cell, or a program without the counters."""
    config = seq_flops.cell_config()
    records = run["records"]
    if not config or config.get("model", {}).get("name") != "keye_vl2" or not records:
        return None
    names = ("dsa_pairs_selected", "dsa_pairs_causal", "moe_rows_routed", "tokens_per_step")
    if any(name not in r for r in records for name in names):
        return None
    mean = lambda name: sum(r[name] for r in records) / len(records) / run["chips"]  # noqa: E731
    return {
        "model": config["model"],
        "tokens": mean("tokens_per_step"),
        "pairs_selected": mean("dsa_pairs_selected"),
        "pairs_causal": mean("dsa_pairs_causal"),
        "rows_routed": mean("moe_rows_routed"),
        "steps": len(records) * run["steps_per_epoch"],
    }
