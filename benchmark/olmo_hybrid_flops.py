"""Required matrix-product FLOPs of one optimizer step of the ``olmo_hybrid``
family, from the configuration's shapes and the program's counters
(``tokens_per_step``, ``gdn_chunks``): ``seq_flops.py`` knows no DeltaNet layer,
no tensor share and no untied head, so it cannot serve.  Kept with the
benchmark so that no later PR moves the numerators of ``gdn_scan_roofline_pct``
or of ``olmo_hybrid_mfu_pct``.

Counted per step over the heads and columns HELD (``model.tensor_shards``
divides the published counts), forward and backward (three times the
forward's products), recomputation not counted:

  the scan   a token and held DeltaNet head, the products of the chunkwise
             form at chunk 64, whatever computes them:
             2 · (2·64·96 + 64·288 + 3·96·192 + 64·192)
             (K·Kᵀ and Q·Kᵀ; T times [K | V]; W·S, Q·S and Kᵀ·Ṽ against the
             96 × 192 state; the chunk's own (Q·Kᵀ)·Ṽ).  The triangular
             inverse is not counted: a method's own cost, not the rule's.
  DeltaNet   the q, k (96 a head), v, gate and output (192 a head)
             projections and the two scalar gates a head
  attention  the q, k, v and o projections and the causal half of
             4 · S² · head size a held head and sequence
  the rest   SwiGLU's three products over the columns held, the untied head

Not counted: norms, taps, SiLU, the L2 norm, gates, softmax, loss and Adam.
"""

from __future__ import annotations

import seq_flops

CHUNK = 64  # the chunk the count is made at (the program's; tiling only)


def _held(model: dict):
    shards = model.get("tensor_shards", 1)
    return (
        model["num_attention_heads"] // shards,
        model["linear_num_value_heads"] // shards,
        model["intermediate_size"] // shards,
    )


def scan_flops(model: dict, tokens: float) -> float:
    """Forward and backward FLOPs of the delta-rule scans over ``tokens``
    positions summed over the DeltaNet layers."""
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    per_head = 2 * (2 * CHUNK * dk + CHUNK * (dk + dv) + 3 * dk * dv + CHUNK * dv)
    return 3.0 * tokens * _held(model)[1] * per_head


def step_flops(model: dict, tokens: float, seq_len: int) -> float:
    """``tokens`` is positions a step (one layer's worth), in sequences of
    ``seq_len``."""
    d = model["hidden_size"]
    heads, linear_heads, columns = _held(model)
    head_dim = model.get("head_dim") or d // model["num_attention_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    kinds = model["layer_types"]
    linear, full = kinds.count("linear_attention"), kinds.count("full_attention")
    per_token = linear * 2 * d * linear_heads * (2 * dk + 3 * dv + 2)
    per_token += full * (4 * 2 * d * heads * head_dim + 4 * seq_len * heads * head_dim // 2)
    per_token += len(kinds) * 3 * 2 * d * columns + 2 * d * model["num_classes"]
    return 3.0 * tokens * per_token + scan_flops(model, linear * tokens)


def of_run(run: dict) -> dict | None:
    """``{"model", "tokens", "seq_len", "steps"}`` of a run of an
    ``olmo_hybrid`` cell, the tokens per step and chip; None for any other
    cell, or a program without the counters."""
    config = seq_flops.cell_config()
    records = run["records"]
    if not config or config.get("model", {}).get("name") != "olmo_hybrid" or not records:
        return None
    if any(name not in r for r in records for name in ("tokens_per_step", "gdn_chunks")):
        return None
    return {
        "model": config["model"],
        "tokens": sum(r["tokens_per_step"] for r in records) / len(records) / run["chips"],
        "seq_len": config["data"]["image_size"][1],
        "steps": len(records) * run["steps_per_epoch"],
    }
