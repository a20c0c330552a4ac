"""Required matrix-product FLOPs of one optimizer step of a sequence model
(``model.name`` ``lfm2_moe``), from the configuration's shapes and the
program's routed-row counter: what ``benchmark/flops.py`` (a walk over
``conv_general_dilated`` alone) cannot count.  Kept with the benchmark so
that no later PR moves the numerator of ``seq_mfu_pct`` or of the roofline
shares.

Counted, forward, per token unless said: every position-wise projection, the
router, the dense feed-forward and the head at 2·k·n; the expert products at
``rows_routed × 3 × 2 × hidden × expert width`` per step (three products a
row); attention's scores and weighted sum at the causal half of
``4 · S² · heads · head size`` per sequence.  All times 3 for forward and
backward.  Recomputation (the layers are rematerialised) is not counted, nor
are norms, the depthwise taps, softmax, routing, loss and Adam.

``run.py`` hands a reader no configuration, so ``cell_config`` finds the
cell's file as ``run.py`` does: ``--workload`` and ``BENCHMARK.json``.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def cell_config(argv=None) -> dict | None:
    """The configuration file of the cell this process runs, or None."""
    argv = sys.argv if argv is None else argv
    name = None
    for i, a in enumerate(argv):
        if a == "--workload" and i + 1 < len(argv):
            name = argv[i + 1]
        elif a.startswith("--workload="):
            name = a.split("=", 1)[1]
    try:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            manifest = json.load(f)
        cell = next(w for w in manifest["workloads"] if w["name"] == name)
        entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
        with open(os.path.join(ROOT, entry["file"])) as f:
            return json.load(f)
    except (OSError, StopIteration, KeyError):
        return None


def operator_flops(model: dict, kind: str) -> int:
    """Forward FLOPs per token of one layer's operator projections."""
    d = model["hidden_size"]
    if kind == "full_attention":
        head = d // model["num_attention_heads"]
        kv = model["num_key_value_heads"] * head
        return 2 * d * d + 2 * 2 * d * kv + 2 * d * d  # q, k and v, o
    return 2 * d * 3 * d + 2 * d * d  # in_proj, out_proj


def attention_score_flops(model: dict, seq_len: int) -> int:
    """Forward FLOPs per SEQUENCE of q·kᵀ and p·v, the causal half."""
    return 4 * seq_len * seq_len * model["hidden_size"] // 2


def expert_flops(model: dict, rows: float) -> float:
    """Forward FLOPs of the three expert products over ``rows`` routed rows."""
    return rows * 3 * 2 * model["hidden_size"] * model["moe_intermediate_size"]


def attention_flops(model: dict, seq_len: int, sequences: int) -> float:
    """Forward and backward FLOPs per step of the attention operators:
    projections and causal scores of every ``full_attention`` layer."""
    layers = sum(k == "full_attention" for k in model["layer_types"])
    per_seq = seq_len * operator_flops(model, "full_attention") + attention_score_flops(
        model, seq_len
    )
    return 3.0 * layers * sequences * per_seq


def step_flops(model: dict, seq_len: int, sequences: int, rows_routed: float) -> float:
    """Forward and backward FLOPs of one optimizer step over ``sequences``
    sequences of ``seq_len`` tokens with ``rows_routed`` (token, held expert)
    pairs summed over the routed layers."""
    d = model["hidden_size"]
    kinds = model["layer_types"]
    dense = model["num_dense_layers"]
    per_token = sum(operator_flops(model, k) for k in kinds)
    per_token += dense * 3 * 2 * d * model["intermediate_size"]
    per_token += (len(kinds) - dense) * 2 * d * model["num_experts"]  # routers
    per_token += 2 * d * model["num_classes"]  # tied head
    attention = sum(k == "full_attention" for k in kinds) * attention_score_flops(model, seq_len)
    forward = sequences * (seq_len * per_token + attention) + expert_flops(model, rows_routed)
    return 3.0 * forward


def of_run(run: dict) -> dict | None:
    """``{"model", "seq_len", "sequences", "rows_routed", "steps"}`` of a
    run of a sequence-model cell; None for any other cell or program."""
    config = cell_config()
    records = run["records"]
    if not config or "layer_types" not in config.get("model", {}) or not records:
        return None
    if any("moe_rows_routed" not in r for r in records):
        return None
    return {
        "model": config["model"],
        "seq_len": config["data"]["image_size"][1],
        "sequences": run["tiles_per_step"] // run["chips"],
        "rows_routed": sum(r["moe_rows_routed"] for r in records) / len(records) / run["chips"],
        "steps": len(records) * run["steps_per_epoch"],
    }
