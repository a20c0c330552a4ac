"""The yardstick against the program, on the CPU at a tiny size: the copied
conv walk counts what ``obs/flops`` counts, each plain reference agrees with
the program and tells a lowered compute dtype, and a rehearsed run prints the
contract's line."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

CONFIGS = ["unet_flagship", "unetpp", "unet_pod4"]


def experiment(name, rehearse=False):
    import run as bench_run

    config = json.load(open(os.path.join(BENCH, "configs", name + ".json")))
    traffic = json.load(open(os.path.join(BENCH, "traffic", "cached.json")))
    return bench_run.build_config(config, traffic, {}, 0, rehearse), config


@pytest.mark.parametrize("name", CONFIGS)
def test_conv_walk_is_the_programs(name):
    import flops
    from ddlpc_tpu.obs import flops as theirs

    cfg, _ = experiment(name)
    assert flops.conv_step_flops(cfg) == theirs.conv_step_flops(
        cfg, cfg.train.micro_batch_size, cfg.train.sync_period
    )


def compare(name, stated, computed):
    """The reference check at the configuration's rehearsal size: the limits
    are those of the ``stated`` dtype, the program computes in ``computed``."""
    import jax
    import jax.numpy as jnp

    import check
    from ddlpc_tpu.data.datasets import build_dataset
    from ddlpc_tpu.models import build_model

    cfg, config = experiment(name, rehearse=True)
    h, w = cfg.data.image_size
    model_cfg = dataclasses.replace(cfg.model, compute_dtype=computed, head_dtype=computed)
    variables = build_model(model_cfg).init(
        jax.random.key(1), jnp.zeros((1, h, w, 3), jnp.float32), train=False
    )
    train, _ = build_dataset(cfg.data)
    out = check.compare(
        model_cfg, config["reference"], variables["params"], variables["batch_stats"],
        train.images[:2], train.labels[:2],
    )
    limits = check.load_reference(config["reference"]).TOLERANCE[stated]
    return out, all(out[k] <= limits[k] for k in limits)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_agrees_in_the_stated_dtype(name):
    for dtype in ("float32", "bfloat16"):
        out, ok = compare(name, dtype, dtype)
        assert ok, (dtype, out)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_tells_a_lowered_dtype(name):
    out, ok = compare(name, "float32", "bfloat16")
    assert not ok, out


def test_rehearsed_run_prints_the_contracts_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "unetpp.cached",
         "--seed", "2147495993", "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["attempted"] >= 2 and line["failed"] == 0
    # No device plane on the CPU: the trace's readers find nothing and are left out.
    assert set(line["metrics"]) == {"trainer_init_s", "first_epoch_s", "data_wait_ms"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


def test_refuses_a_machine_without_a_tpu():
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "unetpp.cached"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=600,
    )
    assert done.returncode != 0 and "{" not in done.stdout
