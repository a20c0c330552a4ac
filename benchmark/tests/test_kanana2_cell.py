"""The kanana2_30b_a3b_ep16 cell's own files, on the CPU at the rehearsal
size: its manifest entries, the reference check in the stated dtype and
lowered ones, a rehearsed run's last line, and ``deepseek_v3_flops`` against
a hand count."""

import dataclasses
import json
import os
import subprocess
import sys

from conftest import BENCH, ROOT

CELL = "kanana2_30b_a3b_ep16.cached"
NAME = "kanana2_30b_a3b_ep16"
CONFIG = os.path.join(BENCH, "configs", NAME + ".json")
NEW_METRICS = ("mla_proj_device_ms", "moe_shared_device_ms", "mla_attention_roofline_pct", "deepseek_v3_mfu_pct")
SHARED_METRICS = ("attention_device_ms", "moe_route_device_ms", "moe_experts_device_ms", "moe_experts_roofline_pct",
                  "moe_max_load", "dense_ffn_device_ms", "gather_device_ms")


def test_the_cells_entries_in_the_manifest():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    config = [c for c in manifest["configs"] if c["name"] == NAME]
    cell = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert len(config) == len(cell) == 1
    assert config[0]["file"] == "benchmark/configs/" + NAME + ".json"
    assert config[0]["reduced"] == json.load(open(CONFIG))["reduced"]
    assert config[0]["source"] == json.load(open(CONFIG))["source"]
    assert (cell[0]["config"], cell[0]["traffic"], cell[0]["chips"]) == (NAME, "cached", 1)
    for entry in (config[0], cell[0]):
        for key in ("why", "source"):
            line = entry.get(key, "x")
            assert 1 <= len(line) <= 200 and all(32 <= ord(c) < 127 for c in line), (entry["name"], key)
    metrics = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL] and metrics[name]["moves"] == "tiles_per_s_per_chip"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    for name in SHARED_METRICS:
        assert metrics[name]["workloads"][-1] == CELL, name
    for name in ("short_conv_device_ms", "attention_roofline_pct", "seq_mfu_pct", "keye_vl2_mfu_pct"):
        assert CELL not in metrics[name]["workloads"], name
    workload = json.load(open(os.path.join(BENCH, "workloads", CELL + ".json")))
    assert workload == {"overrides": {}, "expect": {"steps_per_epoch": 5, "tiles_per_step": 4}}


def rehearsal():
    import run as bench_run

    config = json.load(open(CONFIG))
    traffic = json.load(open(os.path.join(BENCH, "traffic", "cached.json")))
    return bench_run.build_config(config, traffic, {}, 0, True), config


def compare(computed: str, seed: int = 0):
    """check.py's errors on two 1,024-token sequences of the rehearsal model."""
    import jax
    import jax.numpy as jnp

    import check
    from ddlpc_tpu.data.datasets import PackedTokenTiles
    from ddlpc_tpu.models import build_model

    cfg, config = rehearsal()
    model_cfg = dataclasses.replace(cfg.model, compute_dtype=computed)
    seq = cfg.data.image_size[1]
    ds = PackedTokenTiles(num_tiles=2, image_size=(1, seq), num_classes=cfg.model.num_classes, seed=seed)
    params = build_model(cfg.model).init(
        jax.random.key(seed + 1), jnp.zeros((1, 1, seq, 1), jnp.int32), train=False
    )["params"]
    got = check.program_fn(model_cfg)(params, {}, ds.images, ds.labels)
    want = check.reference_fn(config["reference"], dataclasses.asdict(model_cfg))(
        params, {}, ds.images, ds.labels
    )
    return {k: float(v) for k, v in check._errors(got, want).items()}


def within(out: dict, stated: str) -> bool:
    import check

    limits = check.load_reference("deepseek_v3").TOLERANCE[stated]
    return all(out[k] <= limits[k] for k in limits)


def test_reference_agrees_in_float32():
    out = compare("float32")
    assert within(out, "float32"), out


def test_reference_tells_a_lowered_dtype():
    """bfloat16 fails float32's limits; float8 fails bfloat16's, which were
    set on the chip, by its gradients and logits."""
    import check

    limits = check.load_reference("deepseek_v3").TOLERANCE["bfloat16"]
    out = compare("bfloat16")
    assert not within(out, "float32") and out["grad"] <= limits["grad"], out
    out = compare("float8_e4m3fn")
    assert not within(out, "bfloat16") and out["grad"] > limits["grad"] and out["logits"] > limits["logits"], out


def test_rehearsed_run_prints_the_contracts_line():
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147495993", "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=900,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] >= 2 and line["failed"] == 0
    # No chip, no peak: the shares of a peak are left out; the scopes' times are
    # read from the CPU ops' op_names, the load from the records.
    for name in ("mla_proj_device_ms", "moe_shared_device_ms", "attention_device_ms", "moe_route_device_ms",
                 "moe_experts_device_ms", "dense_ffn_device_ms", "moe_max_load", "gather_device_ms"):
        assert line["metrics"][name]["value"] > 0, name
    assert not {"deepseek_v3_mfu_pct", "mla_attention_roofline_pct", "moe_experts_roofline_pct", "mfu_pct",
                "short_conv_device_ms", "seq_mfu_pct", "keye_vl2_mfu_pct"} & set(line["metrics"])
    verdicts = json.loads(lines[-2])["verdicts"]
    assert verdicts["no_failed_epoch"] and verdicts["loss_fell"] and verdicts["no_compilation_in_window"]
    records = [ln for ln in lines if ln.startswith("epoch=")]
    assert records and all("moe_rows_dropped=0.0000" in ln and "mla_kernel_layers=0.0000" in ln for ln in records)


def test_flops_against_a_hand_count():
    import deepseek_v3_flops as flops

    model = json.load(open(CONFIG))["model"]
    d, s, heads = 2048, 8192, 32
    proj = 2 * (d * heads * 192 + d * 576 + 512 * heads * 256 + heads * 128 * d)
    assert flops.projection_flops(model) == proj == 52_690_944
    # a sequence and layer: q.k at 192 and P.V at 128 over S^2/2 pairs, forward and backward
    assert flops.attention_flops(model, 1, s) == 3 * 2 * 320 * heads * s * s / 2
    tokens, rows = 4 * s, 4 * s * 6 * 4 / 16  # 8 of 128 held: a sixteenth of the pairs, 4 routed layers
    per_token = 5 * proj + 3 * 2 * d * 6144 + 4 * (3 * 2 * d * 1536 + 2 * d * 128) + 2 * d * 16032
    want = 3 * (tokens * per_token + rows * 6 * d * 768) + 5 * flops.attention_flops(model, 4, s)
    assert flops.step_flops(model, tokens, s, rows) == want
    assert 89e12 < want < 91e12  # 0.92 GFLOP a token forward
    # the attention core is most of it
    assert 5 * flops.attention_flops(model, 4, s) / want > 0.45


def test_readers_find_nothing_where_the_program_counts_nothing():
    """On a program without the family, or in another cell, a
    reader returns None and the line leaves the metric out."""
    import importlib.util

    import deepseek_v3_flops as flops

    run = {"records": [{"moe_rows_routed": 10.0, "tokens_per_step": 8.0, "loss": 1.0}], "tiles_per_step": 4,
           "chips": 1, "steps_per_epoch": 5, "window_s": 1.0, "peak": {"bf16_flops_per_s": 1.97e14}}
    assert flops.of_run(run) is None  # this process runs no cell
    for name in NEW_METRICS:
        path = os.path.join(BENCH, "layer_metrics", name + ".py")
        spec = importlib.util.spec_from_file_location("reader_" + name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        assert module.read(run) is None, name
