"""The lfm2_24b_a2b_ep8 cell's own files, on the CPU at the rehearsal size:
the reference check in the stated dtype and a lowered one, a rehearsed run's
last line, ``seq_flops`` against a hand count and ``scope_time`` on a
hand-built trace."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

CELL = "lfm2_24b_a2b_ep8.cached"
CONFIG = os.path.join(BENCH, "configs", "lfm2_24b_a2b_ep8.json")


def rehearsal():
    import run as bench_run

    config = json.load(open(CONFIG))
    traffic = json.load(open(os.path.join(BENCH, "traffic", "cached.json")))
    return bench_run.build_config(config, traffic, {}, 0, True), config


def compare(computed: str, seed: int = 0):
    """check.compare on four 256-token sequences of the rehearsal model (a
    longer sample than the rehearsal's 64 tokens: one flipped selection among
    a few hundred rows would swing the reading)."""
    import jax
    import jax.numpy as jnp

    import check
    from ddlpc_tpu.data.datasets import PackedTokenTiles
    from ddlpc_tpu.models import build_model

    cfg, config = rehearsal()
    model_cfg = dataclasses.replace(cfg.model, compute_dtype=computed)
    ds = PackedTokenTiles(num_tiles=4, image_size=(1, 256), num_classes=cfg.model.num_classes, seed=seed)
    params = build_model(cfg.model).init(
        jax.random.key(seed + 1), jnp.zeros((1, 1, 256, 1), jnp.int32), train=False
    )["params"]
    if computed in check.load_reference(config["reference"]).TOLERANCE:
        return check.compare(model_cfg, config["reference"], params, {}, ds.images, ds.labels)
    # a dtype no configuration may state: the errors alone, no limits of its own
    got = check.program_fn(model_cfg)(params, {}, ds.images, ds.labels)
    want = check.reference_fn(config["reference"], dataclasses.asdict(model_cfg))(
        params, {}, ds.images, ds.labels
    )
    return {k: float(v) for k, v in check._errors(got, want).items()}


def within(out: dict, stated: str) -> bool:
    import check

    limits = check.load_reference("lfm2_moe").TOLERANCE[stated]
    return all(out[k] <= limits[k] for k in limits)


def test_reference_agrees_in_float32():
    out = compare("float32")
    assert out["ok"] and within(out, "float32"), out


def test_reference_tells_a_lowered_dtype():
    """bfloat16 fails float32's limits; float8 fails bfloat16's, which were set
    on the chip (bf16 0.057 there, float8 0.285).  At this size bf16 reads
    0.06..0.12 on the logits (a flipped selection moves 1/256 of the rows), so
    it is held to a bound of this test's own."""
    out = compare("bfloat16")
    assert not within(out, "float32"), out
    assert out["logits"] < 0.2 and out["grad"] < 0.3 and out["loss"] < 1e-3, out
    out = compare("float8_e4m3fn")
    assert not within(out, "bfloat16") and out["logits"] > 0.2, out


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
    # No chip, no peak: the roofline shares and seq_mfu_pct are left out; the
    # scopes' times are read from the CPU ops' op_names, the counter from the records.
    for name in ("moe_route_device_ms", "moe_experts_device_ms", "attention_device_ms",
                 "short_conv_device_ms", "moe_max_load", "gather_device_ms", "loss_device_ms"):
        assert line["metrics"][name]["value"] > 0, name
    assert not {"seq_mfu_pct", "moe_experts_roofline_pct", "mfu_pct"} & set(line["metrics"])
    verdicts = json.loads(lines[-2])["verdicts"]
    assert verdicts["no_failed_epoch"] and verdicts["loss_fell"] and verdicts["no_compilation_in_window"]
    records = [l for l in lines if l.startswith("epoch=")]
    assert records and all("moe_rows_dropped=0.0000" in l for l in records)


def test_seq_flops_against_a_hand_count():
    import seq_flops

    model = json.load(open(CONFIG))["model"]
    d, s = 2048, 8192
    conv_op = 2 * d * 6144 + 2 * d * d
    attn_op = 2 * d * d * 2 + 2 * d * 512 * 2
    assert seq_flops.operator_flops(model, "conv") == conv_op == 33_554_432
    assert seq_flops.operator_flops(model, "full_attention") == attn_op == 20_971_520
    assert seq_flops.attention_score_flops(model, s) == 2 * s * s * d
    assert seq_flops.expert_flops(model, 1000) == 1000 * 6 * d * 1536
    per_token = 4 * conv_op + attn_op + 6 * d * 11776 + 4 * 2 * d * 64 + 2 * d * 8192
    rows = 65536 * 4 * 4 / 8  # balanced: an eighth of tokens x 4 x routed layers
    want = 3 * (8 * (s * per_token + 2 * s * s * d) + rows * 6 * d * 1536)
    assert seq_flops.step_flops(model, s, 8, rows) == want
    assert 79e12 < want < 83e12  # 1.21-1.25 GFLOP a token, 65,536 tokens
    assert seq_flops.attention_flops(model, s, 8) == 3 * 8 * (s * attn_op + 2 * s * s * d)


def test_seq_flops_finds_the_cells_configuration():
    import seq_flops

    config = seq_flops.cell_config(["run.py", "--workload", CELL, "--seed", "1"])
    assert config["reference"] == "lfm2_moe"
    assert seq_flops.cell_config(["run.py", "--workload=" + CELL])["model"]["hidden_size"] == 2048
    assert seq_flops.cell_config(["run.py", "--workload", "no.such.cell"]) is None
    assert seq_flops.cell_config(["run.py"]) is None
    run = {"records": [{"moe_rows_routed": 10.0}], "tiles_per_step": 8, "chips": 1, "steps_per_epoch": 5}
    assert seq_flops.of_run(run) is None  # this process runs no cell
    assert seq_flops.of_run(dict(run, records=[{"loss": 1.0}])) is None


def test_the_cells_entries_keep_the_manifests_form():
    """What the driver refuses before any run and ``test_manifest`` holds for
    cells only: every line of prose an entry of this cell carries is 1 to 200
    printable characters."""
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = [c for c in manifest["configs"] if c["name"] == "lfm2_24b_a2b_ep8"]
    entries += [w for w in manifest["workloads"] if w["name"] == CELL]
    assert len(entries) == 2
    for entry in entries:
        for key in ("why", "source"):
            line = entry.get(key, "x")
            assert 1 <= len(line) <= 200 and all(32 <= ord(c) < 127 for c in line), (entry["name"], key)


MS = 1_000_000


def test_scope_time_on_a_hand_built_trace(tmp_path, monkeypatch):
    """A while under the accumulate scope encloses two expert fusions, a route
    fusion and an unscoped copy; an expert fusion before the window is left
    out; the enclosing while's own time belongs to no needle."""
    import program_spans
    import scope_time
    from test_program_spans import add_plane

    pb2 = program_spans._xplane_pb2()
    if pb2 is None:
        pytest.skip("no xplane_pb2 in this installation")
    acc = "jit(step)/shard_map/ddlpc/accumulate/while/body/closed_call/"
    tf_ops = {
        "%while.1": "jit(step)/shard_map/ddlpc/accumulate/while:",
        "%fusion.1 = experts fwd": acc + "checkpoint/layers_1/feed_forward/ddlpc/moe/experts/ragged_dot_general:",
        "%fusion.2 = experts bwd": acc + "transpose(jvp(checkpoint))/layers_1/feed_forward/ddlpc/moe/experts/ragged_dot_general:",
        "%fusion.3 = route": acc + "checkpoint/layers_1/feed_forward/ddlpc/moe/route/sort:",
        "%copy.4": "",
        "%ragged-dot-none.1 = custom-call": "ragged-dot-none:",  # the compiler's kernel, scope stripped
    }
    space = pb2.XSpace()
    add_plane(space, "/device:TPU:0", {
        "XLA Ops": [
            ("%fusion.1 = experts fwd", 1 * MS, 3 * MS, {}),  # before the window
            ("%while.1", 10 * MS, 60 * MS, {}),
            ("%fusion.1 = experts fwd", 10 * MS, 20 * MS, {}),
            ("%fusion.3 = route", 20 * MS, 24 * MS, {}),
            ("%copy.4", 24 * MS, 26 * MS, {}),
            ("%fusion.2 = experts bwd", 30 * MS, 50 * MS, {}),
            ("%ragged-dot-none.1 = custom-call", 50 * MS, 55 * MS, {}),
        ],
        "XLA Modules": [("jit_step(1)", 10 * MS, 60 * MS, {})],
    }, tf_ops)
    add_plane(space, "/host:CPU", {"python": [("bench:window", 5 * MS, 100 * MS, {})]})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    ops = scope_time._ops_in_window(str(path))
    assert scope_time.needle_ms(ops, "ddlpc/moe/experts") == pytest.approx(30.0)
    assert scope_time.needle_ms(ops, "ddlpc/moe/route") == pytest.approx(4.0)
    assert scope_time.needle_ms(ops, *scope_time.EXPERT_NEEDLES) == pytest.approx(35.0)
    assert scope_time.needle_ms(ops, "ddlpc/attention") == 0.0
    monkeypatch.setattr(program_spans, "find_trace", lambda: str(path))
    run = {"records": [{}, {}], "steps_per_epoch": 5}
    assert scope_time.ms_per_step(run, "ddlpc/moe/experts") == pytest.approx(3.0)
    assert scope_time.ms_per_step(run, "ddlpc/attention") is None
    monkeypatch.setattr(program_spans, "find_trace", lambda: None)
    assert scope_time.ms_per_step(run, "ddlpc/moe/experts") is None
