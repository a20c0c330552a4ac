"""Goodput & communication accounting (obs/flops.py, obs/comm.py,
obs/hbm.py; docs/PERF.md "Accounting"): closed-form comm byte exactness,
the FLOP model, goodput debit reconciliation on a real trainer run, the
serve jit-cache counters, the perf regression gate, and the stream-schema
version tolerance."""

import json
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from ddlpc_tpu.config import (
    CompressionConfig,
    DataConfig,
    ExperimentConfig,
    ModelConfig,
    TrainConfig,
)
from ddlpc_tpu.obs import comm as obs_comm
from ddlpc_tpu.obs import flops as obs_flops
from ddlpc_tpu.obs import hbm as obs_hbm
from ddlpc_tpu.obs.registry import MetricsRegistry
from ddlpc_tpu.obs.schema import SCHEMA_VERSION, check_record, is_stale


def tiny_cfg(**train_kw):
    return ExperimentConfig(
        model=ModelConfig(
            features=(8, 16), bottleneck_features=16, num_classes=6
        ),
        data=DataConfig(
            dataset="synthetic", image_size=(32, 32), num_classes=6,
            synthetic_len=24, test_split=8,
        ),
        train=TrainConfig(
            micro_batch_size=1, sync_period=2, dump_images_per_epoch=0,
            **train_kw,
        ),
    )


# ---- comm byte accounting: exact closed-form sizes --------------------------


def test_codec_payload_bytes_closed_form():
    # n elements: int8 -> n*1 + 4 (one global fp32 scale), fp16 -> n*2 + 4,
    # none -> n*4.  Exactness is the acceptance contract.
    n = 19366
    assert obs_comm.codec_payload_bytes(n, "int8") == n + 4
    assert obs_comm.codec_payload_bytes(n, "float16") == 2 * n + 4
    assert obs_comm.codec_payload_bytes(n, "none") == 4 * n
    with pytest.raises(ValueError):
        obs_comm.codec_payload_bytes(n, "int4")


def test_comm_plan_allreduce_and_scatter_closed_form():
    n_grads, n_params = 1000, 1000
    for mode, wire in (("int8", 1004), ("float16", 2004), ("none", 4000)):
        (row,) = obs_comm.comm_plan(
            n_grads, n_params, CompressionConfig(mode=mode), 8, "allreduce"
        )
        assert row["collective"] == "all_reduce"
        assert row["bytes_pre"] == 4000
        assert row["bytes_post"] == wire
    rs, ag = obs_comm.comm_plan(
        n_grads, n_params, CompressionConfig(mode="int8"), 8, "scatter"
    )
    assert rs["collective"] == "reduce_scatter" and rs["bytes_post"] == 1004
    # The ZeRO-1 fresh-params publish is uncompressed by construction.
    assert ag["collective"] == "all_gather"
    assert ag["bytes_pre"] == ag["bytes_post"] == 4000
    # quantize_local=False: fp32 enters the wire even with a codec mode.
    (row,) = obs_comm.comm_plan(
        n_grads, n_params,
        CompressionConfig(mode="int8", quantize_local=False), 8, "allreduce",
    )
    assert row["bytes_post"] == 4000 and row["codec"] == "none"


def test_comm_plan_ring_matches_wire_report():
    from ddlpc_tpu.parallel.compressed_allreduce import ring_wire_report

    cfg = CompressionConfig(mode="int8", transport="ring")
    (row,) = obs_comm.comm_plan(1000, 1000, cfg, 8, "ring")
    rep = ring_wire_report(1000, 8, cfg)
    assert row["bytes_post"] == rep["wire_bytes_per_replica"]
    assert row["bytes_pre"] == rep["fp32_bytes_per_replica"]
    # 8 replicas * 10 levels <= 127 -> int8 hops: 2*(N-1) hops of ceil(n/N).
    assert row["bytes_post"] == 2 * 7 * 125 * 1


def test_comm_plan_singleton_and_gspmd():
    cfg = CompressionConfig(mode="int8")
    assert obs_comm.comm_plan(10, 10, cfg, 1, "allreduce") == []
    (row,) = obs_comm.comm_plan(10, 10, cfg, 4, "gspmd")
    # No per-replica quantize stage exists on the GSPMD path: fp32 wire.
    assert row["bytes_pre"] == row["bytes_post"] == 40
    with pytest.raises(ValueError):
        obs_comm.comm_plan(10, 10, cfg, 4, "nope")


def test_comm_accountant_counters_and_record():
    reg = MetricsRegistry()
    plan = obs_comm.comm_plan(
        1000, 1000, CompressionConfig(mode="int8"), 8, "allreduce"
    )
    acct = obs_comm.CommAccountant(reg, plan, "allreduce")
    acct.on_step()
    acct.on_step(2)
    c = reg.get("ddlpc_comm_bytes_total")
    assert c.value(
        collective="all_reduce", codec="int8", stage="pre_codec"
    ) == 3 * 4000
    assert c.value(
        collective="all_reduce", codec="int8", stage="post_codec"
    ) == 3 * 1004
    acct.record_probe(0.010)
    rec = acct.publish(step_time_s=0.100)
    assert rec["kind"] == "comm" and rec["steps"] == 3
    assert rec["comm_fraction"] == 0.1
    assert rec["overlap_headroom_s"] == 0.01  # min(comm, step - comm)
    assert check_record({**rec, "schema": SCHEMA_VERSION}) == []
    assert reg.get("ddlpc_comm_fraction").value() == pytest.approx(0.1)


# ---- FLOP model -------------------------------------------------------------


def test_conv_step_flops_scales_with_batch_and_sync():
    cfg = tiny_cfg()
    f1 = obs_flops.conv_step_flops(cfg, 2, 1)
    assert f1 > 0
    assert obs_flops.conv_step_flops(cfg, 4, 1) == 2 * f1
    assert obs_flops.conv_step_flops(cfg, 2, 3) == 3 * f1


def test_roofline_script_uses_package_impl():
    import roofline

    assert roofline.collect_convs is obs_flops.collect_convs
    assert roofline.conv_flops is obs_flops.conv_flops


def test_resolve_peak_flops():
    peak, assumed = obs_flops.resolve_peak_flops(5e12)
    assert peak == 5e12 and not assumed
    peak, assumed = obs_flops.resolve_peak_flops(0.0)
    # The CPU test mesh has no peak of its own: it assumes the v5e's,
    # flagged (an unknown ACCELERATOR raises — tests/test_chip_bringup.py).
    assert peak == obs_flops.V5E_PEAK_FLOPS and assumed


def test_restart_gap_from_breadcrumb_and_resilience_stream(tmp_path):
    wd = str(tmp_path)
    assert obs_flops.restart_gap_seconds(wd) == 0.0
    from ddlpc_tpu.resilience.protocol import write_breadcrumb

    write_breadcrumb(wd, "running", epoch=3)
    gap = obs_flops.restart_gap_seconds(wd, now=time.time() + 30.0)
    assert 29.0 < gap < 31.0
    # With an INTERRUPTED crumb, resilience.jsonl timestamps refine the
    # gap (newest wins).
    with open(os.path.join(wd, "resilience.jsonl"), "w") as f:
        f.write(json.dumps({"schema": 1, "kind": "supervisor_attempt",
                            "time": time.time() + 10.0}) + "\n")
    gap = obs_flops.restart_gap_seconds(wd, now=time.time() + 30.0)
    assert 19.0 < gap < 21.0
    # A completed run leaves no gap — even with a stale resilience.jsonl
    # lying around (resuming a finished run days later is a new run, not
    # downtime); the crumb phase gates the whole computation.
    write_breadcrumb(wd, "done")
    assert obs_flops.restart_gap_seconds(wd, now=time.time() + 30.0) == 0.0


def test_perf_accountant_gauges_and_reconciliation():
    reg = MetricsRegistry()
    acct = obs_flops.PerfAccountant(
        reg, flops_per_step=10**9, peak_flops=10**12, peak_assumed=True,
        restart_gap_s=5.0,
    )
    acct.start()
    acct.productive(8.0, steps=4)
    acct.debit("data", 1.0)
    acct.debit("eval", 0.5)
    rec = acct.publish(step_time_s=2.0)
    assert rec["kind"] == "perf"
    # MFU: 1e9 / (2.0 s * 1e12) = 5e-4.
    assert rec["mfu"] == pytest.approx(5e-4)
    assert reg.get("ddlpc_mfu").value() == pytest.approx(5e-4)
    # The restart gap is both a debit category and part of the wall.
    assert rec["debit_restart_s"] == 5.0
    assert rec["wall_s"] >= 5.0
    # Goodput is productive/wall by definition (these fabricated inputs
    # are not real intervals; the trainer integration test pins the
    # productive + debits <= wall reconciliation on measured ones).
    assert rec["goodput"] == pytest.approx(
        rec["productive_s"] / rec["wall_s"], rel=1e-3
    )
    assert check_record({**rec, "schema": SCHEMA_VERSION}) == []


# ---- live trainer integration (the satellite reconciliation run) ------------


def test_trainer_publishes_accounting_and_debits_reconcile(tmp_path):
    """Short REAL trainer run: live ddlpc_mfu / ddlpc_goodput /
    ddlpc_hbm_bytes / ddlpc_comm_bytes_total on the registry, comm bytes
    matching the closed form exactly, and attributed seconds summing to
    <= wall."""
    import jax

    from ddlpc_tpu.train.trainer import Trainer

    cfg = tiny_cfg(
        epochs=2, eval_every_epochs=1, checkpoint_every_epochs=2,
        trace=True, trace_sync_every_steps=1,
    ).replace(
        compression=CompressionConfig(mode="int8"),
        workdir=str(tmp_path),
    )
    t = Trainer(cfg, resume=False)
    try:
        assert t.perf is not None and t.comm is not None
        t.fit()
        snap = t.registry.snapshot()
        assert snap["ddlpc_goodput"] > 0
        assert snap["ddlpc_mfu"] > 0
        assert snap['ddlpc_hbm_bytes{kind="params"}'] > 0
        assert snap['ddlpc_hbm_bytes{kind="opt_state"}'] > 0

        # Exact closed-form comm bytes: steps x plan row.
        n_params = obs_comm.tree_elements(t.state.params)
        steps = 2 * len(t.loader)
        data_size = t.mesh.shape["data"]
        variant = "scatter" if t.shard_update else "allreduce"
        plan = obs_comm.comm_plan(
            n_params, n_params, cfg.compression, data_size, variant
        )
        counter = t.registry.get("ddlpc_comm_bytes_total")
        for row in plan:
            assert counter.value(
                collective=row["collective"], codec=row["codec"],
                stage="post_codec",
            ) == steps * row["bytes_post"]
            assert counter.value(
                collective=row["collective"], codec=row["codec"],
                stage="pre_codec",
            ) == steps * row["bytes_pre"]
        # HBM gauges match the package accounting for the placed state.
        assert snap['ddlpc_hbm_bytes{kind="opt_state"}'] == (
            obs_hbm.leaf_bytes_per_device(t.state.opt_state)
        )

        # Stream records: perf + comm present, reconciliation holds.
        recs = [
            json.loads(l)
            for l in open(os.path.join(str(tmp_path), "metrics.jsonl"))
        ]
        perf = [r for r in recs if r.get("kind") == "perf"]
        comm = [r for r in recs if r.get("kind") == "comm"]
        assert len(perf) == 2 and len(comm) == 2
        for r in perf + comm:
            assert check_record(r) == []
        last = perf[-1]
        attributed = last["productive_s"] + sum(
            v for k, v in last.items() if k.startswith("debit_")
        )
        assert attributed <= last["wall_s"] + 0.05
        assert last["steps"] == steps
        # The traced run sampled the fenced comm probe.
        assert comm[-1].get("comm_s_per_step", 0) > 0
        assert 0 <= comm[-1]["comm_fraction"] <= 1
    finally:
        t.close()


def test_trainer_perf_accounting_off_is_silent(tmp_path):
    from ddlpc_tpu.train.trainer import Trainer

    cfg = tiny_cfg(
        epochs=1, eval_every_epochs=0, checkpoint_every_epochs=0,
        perf_accounting=False,
    ).replace(workdir=str(tmp_path))
    t = Trainer(cfg, resume=False)
    try:
        assert t.perf is None and t.comm is None
        t.fit()
        snap = t.registry.snapshot()
        assert "ddlpc_mfu" not in snap
        assert not any(k.startswith("ddlpc_comm") for k in snap)
    finally:
        t.close()


# ---- serve jit cache counters ----------------------------------------------


def test_serve_jit_cache_hit_miss_counters(tmp_path):
    import serve_bench

    from ddlpc_tpu.serve.engine import InferenceEngine

    workdir = str(tmp_path / "run")
    serve_bench.make_tiny_run(workdir)
    eng = InferenceEngine.from_workdir(workdir, max_bucket=4, echo=False)
    reg = MetricsRegistry()
    eng.attach_registry(reg)
    x = np.zeros((1, 32, 32, 3), np.float32)
    eng.forward_windows(x)  # miss: compiles bucket 1
    eng.forward_windows(x)  # hit
    eng.forward_windows(np.zeros((2, 32, 32, 3), np.float32))  # miss: bucket 2
    hits = reg.get("ddlpc_serve_jit_cache_hits_total")
    misses = reg.get("ddlpc_serve_jit_cache_misses_total")
    assert misses.value(bucket="1") == 1
    assert hits.value(bucket="1") == 1
    assert misses.value(bucket="2") == 1
    text = reg.exposition()
    assert 'ddlpc_serve_jit_cache_hits_total{bucket="1"} 1' in text


# ---- perf gate --------------------------------------------------------------


def test_perf_gate_compare_directions_and_tolerance():
    import perf_gate

    metrics = {
        "update_step_ms": dict(
            value=100.0, unit="ms", direction="lower", tolerance=0.08
        ),
        "loader_tiles_per_s": dict(
            value=1000.0, unit="tiles/s", direction="higher", tolerance=0.3
        ),
    }
    assert perf_gate.compare(metrics, {"update_step_ms": 100.0}) == []
    assert perf_gate.compare(metrics, {"update_step_ms": 107.0}) == []
    # A >= 10% update-step regression fails loudly, naming the metric.
    fails = perf_gate.compare(metrics, {"update_step_ms": 110.0})
    assert len(fails) == 1 and "update_step_ms" in fails[0]
    # Improvements always pass (one-sided band).
    assert perf_gate.compare(metrics, {"update_step_ms": 50.0}) == []
    assert perf_gate.compare(metrics, {"loader_tiles_per_s": 5000.0}) == []
    fails = perf_gate.compare(metrics, {"loader_tiles_per_s": 600.0})
    assert len(fails) == 1 and "loader_tiles_per_s" in fails[0]
    # Unmeasured (skipped) arms are not compared.
    assert perf_gate.compare(metrics, {}) == []
    # Injection multiplies the measured value.
    fails = perf_gate.compare(
        metrics, {"update_step_ms": 100.0}, inject={"update_step_ms": 1.10}
    )
    assert len(fails) == 1


def test_perf_gate_validate_baseline():
    import perf_gate

    good = {
        "schema": perf_gate.BASELINE_SCHEMA,
        "metrics": {
            "m": dict(value=1.0, unit="ms", direction="lower", tolerance=0.1)
        },
    }
    assert perf_gate.validate_baseline(good) == []
    assert perf_gate.validate_baseline([]) != []
    assert perf_gate.validate_baseline({"schema": 99, "metrics": {}}) != []
    bad = {
        "schema": perf_gate.BASELINE_SCHEMA,
        "metrics": {"m": dict(value=-1, direction="up", tolerance=2)},
    }
    assert len(perf_gate.validate_baseline(bad)) == 3


def test_perf_gate_smoke_green_on_committed_baseline():
    """Tier-1 invocation: the COMMITTED baseline must validate and the
    gate's regression detection must self-check — a broken gate or stale
    baseline schema fails the suite here."""
    import perf_gate

    assert os.path.exists(perf_gate.DEFAULT_BASELINE), (
        "docs/perf/baseline.json is not committed"
    )
    assert perf_gate.main(["--smoke"]) == 0


def test_perf_gate_smoke_catches_broken_baseline(tmp_path):
    import perf_gate

    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({"schema": 1, "metrics": {}}))
    assert perf_gate.main(["--smoke", "--baseline", str(p)]) == 1
    p.write_text("not json")
    assert perf_gate.main(["--smoke", "--baseline", str(p)]) == 1


def test_perf_gate_inject_only_demonstration(capsys):
    """The acceptance demonstration, as a pinned test: a 10% injected
    update-step regression fails with a non-zero exit naming the metric;
    the unmodified baseline passes."""
    import perf_gate

    assert perf_gate.main(
        ["--inject-only", "--inject", "update_step_ms=1.10"]
    ) == 1
    out = capsys.readouterr().out
    assert "REGRESSION update_step_ms" in out
    assert perf_gate.main(
        ["--inject-only", "--inject", "update_step_ms=1.01"]
    ) == 0


# ---- stream hygiene: older-schema tolerance ---------------------------------


def test_schema_tolerates_older_versions_rejects_newer_and_unknown_kinds():
    assert check_record({"schema": 0, "loss": 1.0}) == []  # older: tolerated
    assert is_stale({"schema": 0})
    assert not is_stale({"schema": SCHEMA_VERSION})
    errs = check_record({"schema": SCHEMA_VERSION + 1})
    assert any("newer" in e for e in errs)
    # Negative stamps are emitter bugs, not old versions.
    errs = check_record({"schema": -1})
    assert any("not a valid version" in e for e in errs)
    assert not is_stale({"schema": -1})
    errs = check_record({"schema": SCHEMA_VERSION, "kind": "mystery"})
    assert any("unknown record kind" in e for e in errs)
    assert check_record({"schema": SCHEMA_VERSION, "kind": "perf"}) == []


def test_schema_lint_reports_stale_without_failing(tmp_path, capsys):
    import check_metrics_schema as lint

    p = tmp_path / "old.jsonl"
    p.write_text(
        json.dumps({"schema": 0, "loss": 1.0}) + "\n"
        + json.dumps({"schema": SCHEMA_VERSION, "loss": 0.5}) + "\n"
    )
    assert lint.main([str(p)]) == 0  # tolerated, not failed
    assert "1 record(s) from older schema versions tolerated" in (
        capsys.readouterr().err
    )
    # A NEWER version than the tooling still fails.
    p.write_text(json.dumps({"schema": SCHEMA_VERSION + 1}) + "\n")
    assert lint.main([str(p)]) == 1


def test_obs_tail_reports_stale_and_keeps_streaming(tmp_path, capsys):
    import obs_tail

    p = tmp_path / "m.jsonl"
    p.write_text(
        json.dumps({"schema": 0, "loss": 1.0}) + "\n"
        + json.dumps({"schema": SCHEMA_VERSION, "loss": 0.5}) + "\n"
    )
    assert obs_tail.main([str(p), "-n", "0"]) == 0
    captured = capsys.readouterr()
    # Both records emitted; the stale one noted once on stderr.
    assert captured.out.count("\n") == 2
    assert "older schema version 0" in captured.err
