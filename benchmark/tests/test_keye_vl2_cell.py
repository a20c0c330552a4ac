"""The keye_vl2_30b_a3b_ep8 cell's own files, on the CPU at the rehearsal
size: its manifest entries, the reference check in the stated dtype and a
lowered one (check.py's three errors and check_indexer.py's two), a rehearsed
run's last line, and ``keye_vl2_flops`` against a hand count."""

import dataclasses
import json
import os
import subprocess
import sys

from conftest import BENCH, ROOT

CELL = "keye_vl2_30b_a3b_ep8.cached"
NAME = "keye_vl2_30b_a3b_ep8"
CONFIG = os.path.join(BENCH, "configs", NAME + ".json")
NEW_METRICS = ("dsa_indexer_device_ms", "dsa_select_device_ms", "dsa_kl_device_ms", "dsa_selected_share",
               "dsa_attention_roofline_pct", "dsa_indexer_roofline_pct", "keye_vl2_mfu_pct")
SHARED_METRICS = ("gather_device_ms", "moe_route_device_ms", "moe_experts_device_ms",
                  "moe_experts_roofline_pct", "moe_max_load", "attention_device_ms")


def test_the_cells_entries_in_the_manifest():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    config = [c for c in manifest["configs"] if c["name"] == NAME]
    cell = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert len(config) == len(cell) == 1 and manifest["workloads"][-1] is cell[0]
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
    for name in ("short_conv_device_ms", "attention_roofline_pct", "seq_mfu_pct"):
        assert CELL not in metrics[name]["workloads"], name
    workload = json.load(open(os.path.join(BENCH, "workloads", CELL + ".json")))
    assert workload == {"overrides": {}, "expect": {"steps_per_epoch": 5, "tiles_per_step": 2}}


def rehearsal():
    import run as bench_run

    config = json.load(open(CONFIG))
    traffic = json.load(open(os.path.join(BENCH, "traffic", "cached.json")))
    return bench_run.build_config(config, traffic, {}, 0, True), config


def compare(computed: str, seed: int = 0):
    """check.py's errors and check_indexer.py's on two 1,024-token sequences of
    the rehearsal model (two query blocks of 512, 256 keys picked a query)."""
    import jax
    import jax.numpy as jnp

    import check
    import check_indexer
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
    out = {k: float(v) for k, v in check._errors(got, want).items()}
    own = check_indexer.compare(model_cfg, config["reference"], params, ds.images)
    return out | {k: own[k] for k in ("indexer_loss", "indexer_grad", "grad_outside_indexer")}


def within(out: dict, stated: str) -> bool:
    import check

    ref = check.load_reference("keye_vl2")
    limits = ref.TOLERANCE[stated] | ref.INDEXER_TOLERANCE[stated]
    return all(out[k] <= limits[k] for k in limits)


def test_reference_agrees_in_float32():
    out = compare("float32")
    assert within(out, "float32") and out["grad_outside_indexer"] == [0.0, 0.0], out


def test_reference_tells_a_lowered_dtype():
    """bfloat16 fails float32's limits and keeps its own; float8 fails
    bfloat16's, which were set on the chip (at this size bf16 reads 0.015 on
    the logits, 0.005 on the gradients and 0.012 on the indexer's; float8
    0.092, 0.30 and 1.0)."""
    out = compare("bfloat16")
    assert not within(out, "float32") and within(out, "bfloat16"), out
    out = compare("float8_e4m3fn")
    assert not within(out, "bfloat16") and out["grad"] > 0.15 and out["indexer_grad"] > 0.5, out


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
    # read from the CPU ops' op_names, the share and the load from the records.
    for name in ("dsa_indexer_device_ms", "dsa_select_device_ms", "dsa_kl_device_ms", "attention_device_ms",
                 "moe_route_device_ms", "moe_experts_device_ms", "moe_max_load", "gather_device_ms"):
        assert line["metrics"][name]["value"] > 0, name
    assert abs(line["metrics"]["dsa_selected_share"]["value"] - 229504 / 524800) < 1e-3  # S 1024, topk 256
    assert not {"keye_vl2_mfu_pct", "dsa_attention_roofline_pct", "dsa_indexer_roofline_pct",
                "moe_experts_roofline_pct", "mfu_pct", "short_conv_device_ms", "seq_mfu_pct"} & set(line["metrics"])
    verdicts = json.loads(lines[-2])["verdicts"]
    assert verdicts["no_failed_epoch"] and verdicts["loss_fell"] and verdicts["no_compilation_in_window"]
    assert verdicts["reference"]
    records = [l for l in lines if l.startswith("epoch=")]
    assert records and all("moe_rows_dropped=0.0000" in l and "indexer_kl=" in l for l in records)


def test_flops_against_a_hand_count():
    import keye_vl2_flops as flops

    model = json.load(open(CONFIG))["model"]
    d, s, layers = 2048, 16384, 4
    selected, causal = 31_458_304, 134_225_920  # a layer and sequence
    proj = 2 * d * 4096 * 2 + 2 * d * 512 * 2
    assert flops.attention_flops(model, s, selected) == 3 * (s * proj + 4 * 128 * 32 * selected)
    index_proj = 2 * d * (1024 + 64 + 16)
    assert flops.indexer_flops(model, s, causal) == 2 * s * index_proj + 3 * 2 * 1024 * causal
    rows = 2 * s * 8 * layers / 8  # balanced: an eighth of tokens x 8 x layers
    want = (
        flops.attention_flops(model, 2 * s * layers, 2 * layers * selected)
        + flops.indexer_flops(model, 2 * s * layers, 2 * layers * causal)
        + 3 * (2 * s * (layers * 2 * d * 128 + 2 * d * 18992) + rows * 6 * d * 768)
    )
    assert flops.step_flops(model, 2 * s, 2 * layers * selected, 2 * layers * causal, rows) == want
    assert 46e12 < want < 47e12  # 0.71 GFLOP a token and step, forward and backward
    # forward, a layer and sequence: the selected pairs' products are most of it
    assert 4 * 128 * 32 * selected / 1e12 > 0.5 and 2 * 1024 * causal / 1e12 > 0.27


def test_readers_find_nothing_where_the_program_counts_nothing():
    """On the parent of the PR that added the family, or in another cell, a
    reader returns None and the line leaves the metric out."""
    import importlib.util

    import keye_vl2_flops as flops

    run = {"records": [{"moe_rows_routed": 10.0, "loss": 1.0}], "tiles_per_step": 2, "chips": 1,
           "steps_per_epoch": 5, "window_s": 1.0, "peak": {"bf16_flops_per_s": 1.97e14}}
    assert flops.of_run(run) is None  # this process runs no cell
    for name in NEW_METRICS:
        path = os.path.join(BENCH, "layer_metrics", name + ".py")
        spec = importlib.util.spec_from_file_location("reader_" + name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        assert module.read(run) is None, name
