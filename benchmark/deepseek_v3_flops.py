"""Required matrix-product FLOPs of one optimizer step of the ``deepseek_v3``
family, from the configuration's shapes and the program's counters
(``tokens_per_step``, ``moe_rows_routed``): ``seq_flops.py`` takes the head
size as ``hidden / heads``, one width for q·k and v and a tied head, so it
cannot serve.  Kept with the benchmark so that no change to the program moves the
numerators of ``mla_attention_roofline_pct`` or of ``deepseek_v3_mfu_pct``.

Counted per step, forward and backward (three times the forward's products),
recomputation not counted:

  MLA        a token and layer, W_q (hidden → heads · 192), W_kva (hidden →
             512 + 64), W_kvb (512 → heads · (128 + 128)) and W_o (heads ·
             128 → hidden); and the attention core, 2 · (192 + 128) · heads a
             causal pair (q·kᵀ at 192, P·V at 128) over the causal half, S²/2
             pairs a sequence, as the kernels' cost estimate states it
  MoE        a token and routed layer, the shared experts' three products at
             n_shared · expert width, and the router over all its experts;
             the routed experts' three products a routed row
  the rest   the dense layers' SwiGLU and the untied head over the slice

Not counted: norms, rotary, softmax, routing's sort and gathers, loss and Adam.
"""

from __future__ import annotations

import seq_flops


def projection_flops(model: dict) -> int:
    """Forward FLOPs a token of one layer's MLA projections."""
    d, heads = model["hidden_size"], model["num_attention_heads"]
    nope, rope, dv = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    latent = model["kv_lora_rank"]
    return 2 * (d * heads * (nope + rope) + d * (latent + rope) + latent * heads * (nope + dv) + heads * dv * d)


def attention_flops(model: dict, sequences: float, seq_len: int) -> float:
    """Forward and backward FLOPs of one layer's attention core over
    ``sequences`` sequences of ``seq_len``: the kernels' work, the
    ``ddlpc/attention`` scope's."""
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    per_pair = 2 * (qk + model["v_head_dim"]) * model["num_attention_heads"]
    return 3.0 * sequences * per_pair * seq_len * seq_len / 2


def step_flops(model: dict, tokens: float, seq_len: int, rows_routed: float) -> float:
    """``tokens`` is positions a step (one layer's worth), in sequences of
    ``seq_len``; ``rows_routed`` the (token, held expert) pairs summed over the
    routed layers."""
    d = model["hidden_size"]
    layers = len(model["layer_types"])
    dense = model["num_dense_layers"]
    routed = layers - dense
    per_token = layers * projection_flops(model)
    per_token += dense * 3 * 2 * d * model["intermediate_size"]
    per_token += routed * (3 * 2 * d * model["n_shared_experts"] * model["moe_intermediate_size"])
    per_token += routed * 2 * d * model["num_experts"]  # routers
    per_token += 2 * d * model["num_classes"]  # untied head
    return (
        3.0 * (tokens * per_token + seq_flops.expert_flops(model, rows_routed))
        + layers * attention_flops(model, tokens / seq_len, seq_len)
    )


def of_run(run: dict) -> dict | None:
    """``{"model", "tokens", "seq_len", "rows_routed", "steps"}`` of a run of
    a ``deepseek_v3`` cell, the counters per step and chip; None for any other
    cell, or a program without the counters."""
    config = seq_flops.cell_config()
    records = run["records"]
    if not config or config.get("model", {}).get("name") != "deepseek_v3" or not records:
        return None
    if any(name not in r for r in records for name in ("tokens_per_step", "moe_rows_routed")):
        return None
    mean = lambda name: sum(r[name] for r in records) / len(records) / run["chips"]  # noqa: E731
    return {
        "model": config["model"],
        "tokens": mean("tokens_per_step"),
        "seq_len": config["data"]["image_size"][1],
        "rows_routed": mean("moe_rows_routed"),
        "steps": len(records) * run["steps_per_epoch"],
    }
