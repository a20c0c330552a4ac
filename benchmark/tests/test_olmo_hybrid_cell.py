"""The olmo_hybrid_7b_tp2 cell's own files, on the CPU at the rehearsal size:
its manifest entries, the reference check in the stated dtype and in lowered
ones, a rehearsed run's last line, and ``olmo_hybrid_flops`` against a hand
count."""

import dataclasses
import json
import os
import subprocess
import sys

from conftest import BENCH, ROOT

CELL = "olmo_hybrid_7b_tp2.cached"
NAME = "olmo_hybrid_7b_tp2"
CONFIG = os.path.join(BENCH, "configs", NAME + ".json")
NEW_METRICS = ("gdn_scan_device_ms", "gdn_conv_device_ms", "gdn_proj_device_ms", "dense_ffn_device_ms",
               "gdn_scan_roofline_pct", "olmo_hybrid_mfu_pct")
SHARED_METRICS = ("gather_device_ms", "attention_device_ms")


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
    for said in ("published ratio", "host's share", "all-reduces"):
        assert said in cell[0]["why"], said
    metrics = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL] and metrics[name]["moves"] == "tiles_per_s_per_chip"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    for name in SHARED_METRICS:
        assert CELL in metrics[name]["workloads"], name
    for name in ("short_conv_device_ms", "attention_roofline_pct", "seq_mfu_pct", "moe_route_device_ms",
                 "moe_experts_device_ms", "moe_max_load", "keye_vl2_mfu_pct", "dsa_kl_device_ms"):
        assert CELL not in metrics[name]["workloads"], name
    workload = json.load(open(os.path.join(BENCH, "workloads", CELL + ".json")))
    assert workload == {"overrides": {}, "expect": {"steps_per_epoch": 5, "tiles_per_step": 4}}


def rehearsal():
    import run as bench_run

    config = json.load(open(CONFIG))
    traffic = json.load(open(os.path.join(BENCH, "traffic", "cached.json")))
    return bench_run.build_config(config, traffic, {}, 0, True), config


def compare(computed: str, seed: int = 0):
    """check.py's errors on two 256-token sequences of the rehearsal model
    (four chunks of 64; two held heads of 32 / 64)."""
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

    limits = check.load_reference("olmo_hybrid").TOLERANCE[stated]
    return all(out[k] <= limits[k] for k in limits)


def test_reference_agrees_in_float32():
    out = compare("float32")
    assert within(out, "float32") and out["logits"] < 2e-5, out


def test_reference_tells_a_lowered_dtype():
    """bfloat16 fails float32's limits and keeps its own; both float8 formats
    fail bfloat16's, which were set on the chip: e5m2 (two bits of mantissa)
    by the logits and the gradients, e4m3 by not being a number (the
    feed-forward's SiLU reads the un-normed stream and its exponential passes
    448).  The readings at this size are in the asserts' messages."""
    out = compare("bfloat16")
    assert not within(out, "float32") and within(out, "bfloat16"), out
    out = compare("float8_e5m2")
    assert not within(out, "bfloat16") and out["logits"] > 0.2 and out["grad"] > 0.6, out
    out = compare("float8_e4m3fn")
    assert not within(out, "bfloat16"), out


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
    # read from the CPU ops' op_names.
    for name in ("gdn_scan_device_ms", "gdn_conv_device_ms", "gdn_proj_device_ms", "dense_ffn_device_ms",
                 "attention_device_ms", "gather_device_ms"):
        assert line["metrics"][name]["value"] > 0, name
    assert not {"olmo_hybrid_mfu_pct", "gdn_scan_roofline_pct", "mfu_pct", "short_conv_device_ms",
                "seq_mfu_pct", "moe_route_device_ms", "keye_vl2_mfu_pct"} & set(line["metrics"])
    verdicts = json.loads(lines[-2])["verdicts"]
    assert verdicts["no_failed_epoch"] and verdicts["loss_fell"] and verdicts["no_compilation_in_window"]
    records = [l for l in lines if l.startswith("epoch=")]
    assert records and all("gdn_chunks=48.0000" in l and "gdn_layers=3.0000" in l for l in records)


def test_flops_against_a_hand_count():
    import olmo_hybrid_flops as flops

    model = json.load(open(CONFIG))["model"]
    d, s, tokens = 3840, 8192, 4 * 8192
    scan = 2 * (2 * 64 * 96 + 64 * 288 + 3 * 96 * 192 + 64 * 192)  # a token and head
    assert scan == 196_608
    assert flops.scan_flops(model, 3 * tokens) == 3 * 3 * tokens * 15 * scan
    delta_net = 2 * d * 15 * (2 * 96 + 3 * 192 + 2)
    attention = 4 * 2 * d * 1920 + 4 * s * 1920 // 2
    rest = 4 * 3 * 2 * d * 5504 + 2 * d * 12544
    want = 3 * tokens * (3 * delta_net + attention + rest) + flops.scan_flops(model, 3 * tokens)
    assert flops.step_flops(model, tokens, s) == want
    assert 94e12 < want < 96e12  # 2.9 GFLOP a token and step, forward and backward
    assert flops.scan_flops(model, 3 * tokens) / want < 0.01  # the rule's own products are under 1 %


def test_readers_find_nothing_where_the_program_counts_nothing():
    """On the parent of the PR that added the family, or in another cell, a
    reader returns None and the line leaves the metric out."""
    import importlib.util

    import olmo_hybrid_flops as flops

    run = {"records": [{"tokens_per_step": 32768.0, "loss": 1.0}], "tiles_per_step": 4, "chips": 1,
           "steps_per_epoch": 5, "window_s": 1.0, "peak": {"bf16_flops_per_s": 1.97e14}}
    assert flops.of_run(run) is None  # this process runs no cell
    for name in NEW_METRICS:
        path = os.path.join(BENCH, "layer_metrics", name + ".py")
        spec = importlib.util.spec_from_file_location("reader_" + name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        assert module.read(run) is None, name
