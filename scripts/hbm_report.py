"""Per-buffer HBM breakdown for a config's train step, replicated vs ZeRO.

For each ``--layout`` arm this builds the real compiled SPMD train step
for ``--config`` on an ``--devices``-sized mesh, then reports where the
per-device state bytes live: params, optimizer-boundary grads, optimizer
moments, batch stats — computed exactly from every leaf's global shape ×
its committed sharding (``sharding.shard_shape``, backend-independent),
plus whatever aggregate numbers the backend's
``compiled.memory_analysis()`` exposes.  The committed artifact
(docs/sharding/hbm_report.json) is the evidence for the ZeRO ladder's
1/N trajectory (docs/SHARDING.md has the budget math):

- ``zero1``: opt_state ÷ N (params/grads full);
- ``zero2``: opt_state AND the persistent grads ÷ N;
- ``zero3``: params too ÷ N — everything that persists scales 1/N
  (``grads_accum``, the transient backward accumulator, stays full on
  every layout and is reported honestly alongside).

Runs on a virtual CPU mesh by default — buffer layout is decided at
partitioning time, identically on every backend.

Usage:
  python scripts/hbm_report.py [--config configs/vaihingen_unet_tpu_flagship.json]
      [--devices 8] [--micro-batch 4] [--layout zero1 zero2 zero3]
      [--out docs/sharding/hbm_report.json]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from ddlpc_tpu.utils.fsio import atomic_write_json  # noqa: E402


def _leaf_bytes_per_device(tree) -> int:
    # Shape×sharding accounting lives in the package now
    # (ddlpc_tpu/obs/hbm.py) — one implementation for this CLI and the
    # trainer's live ddlpc_hbm_bytes gauges.
    from ddlpc_tpu.obs.hbm import leaf_bytes_per_device

    return leaf_bytes_per_device(tree)


def _memory_analysis(compiled) -> dict:
    """Aggregate backend numbers when available (TPU reports full per-space
    stats; the CPU backend may not implement them — record what exists)."""
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # Unimplemented on some backends
        return {"available": False, "error": f"{type(e).__name__}: {e}"}
    if ma is None:
        return {"available": False}
    out = {"available": True}
    for field in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(ma, field, None)
        if v is not None:
            out[field] = int(v)
    return out


def run_arm(cfg, shard_update: str, micro_batch: int, sync_period: int) -> dict:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ddlpc_tpu.models import build_model_from_experiment
    from ddlpc_tpu.obs import hbm as obs_hbm
    from ddlpc_tpu.parallel.mesh import make_mesh
    from ddlpc_tpu.parallel.shard_update import StateLayout, resolve_shard_update
    from ddlpc_tpu.parallel.train_step import create_train_state, make_train_step
    from ddlpc_tpu.train.optim import build_optimizer

    cfg = cfg.replace(
        parallel=dataclasses.replace(
            cfg.parallel, data_axis_size=-1, space_axis_size=1,
            shard_update=shard_update,
        ),
        train=dataclasses.replace(
            cfg.train, micro_batch_size=micro_batch, sync_period=sync_period
        ),
    )
    mesh = make_mesh(cfg.parallel)
    n = mesh.shape[cfg.parallel.data_axis_name]
    level = resolve_shard_update(
        shard_update, cfg.compression, n, spatial=False,
        grad_clip_norm=cfg.train.grad_clip_norm,
    )
    model = build_model_from_experiment(cfg)
    tx = build_optimizer(cfg.train)
    h, w = cfg.data.image_size
    state = create_train_state(model, tx, jax.random.key(0), (1, h, w, 3))
    layout = StateLayout(
        "replicated" if level == "off" else level, tx, state, mesh,
        cfg.parallel.data_axis_name,
    )
    state = layout.place(state)
    step = make_train_step(
        model, tx, mesh, cfg.compression, shard_update=level,
        param_avals=layout.param_avals,
    )
    A, B = sync_period, micro_batch * n
    images = jax.ShapeDtypeStruct(
        (A, B, h, w, 3), np.float32,
        sharding=NamedSharding(mesh, P(None, cfg.parallel.data_axis_name)),
    )
    labels = jax.ShapeDtypeStruct(
        (A, B, h, w), np.int32,
        sharding=NamedSharding(mesh, P(None, cfg.parallel.data_axis_name)),
    )
    compiled = step.lower(state, images, labels).compile()
    per_buffer = {
        # params/opt_state read their committed shardings off the placed
        # state; the gradient kinds come from the same accounting the
        # live ddlpc_hbm_bytes gauges publish (obs/hbm.py).
        "params": _leaf_bytes_per_device(state.params),
        "grads": obs_hbm.grads_bytes_per_device(
            layout.param_avals, level, n
        ),
        "grads_accum": obs_hbm.grads_accum_bytes_per_device(
            layout.param_avals
        ),
        "opt_state": _leaf_bytes_per_device(state.opt_state),
        "batch_stats": _leaf_bytes_per_device(state.batch_stats),
        "batch_images": images.dtype.itemsize * A * (B // n) * h * w * 3,
        "batch_labels": labels.dtype.itemsize * A * (B // n) * h * w,
    }
    return {
        "shard_update": level,
        "devices": n,
        "replicated_by_rule_bytes": layout.replicated_by_rule_bytes(),
        "state_bytes_per_device": per_buffer,
        "state_bytes_per_device_total": sum(per_buffer.values()),
        "memory_analysis": _memory_analysis(compiled),
    }


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument(
        "--config", default="configs/vaihingen_unet_tpu_flagship.json"
    )
    p.add_argument("--devices", type=int, default=8)
    p.add_argument(
        "--micro-batch", type=int, default=4,
        help="per-replica micro-batch for the compiled program (state "
        "buffers are batch-independent; small keeps CPU compiles quick)",
    )
    p.add_argument("--sync-period", type=int, default=2)
    p.add_argument(
        "--layout", nargs="+", default=["zero1", "zero2", "zero3"],
        choices=["zero1", "zero2", "zero3"],
        help="ZeRO levels to report next to the replicated baseline "
        "(the 'off' arm always runs)",
    )
    p.add_argument("--out", default="docs/sharding/hbm_report.json")
    args = p.parse_args()

    from ddlpc_tpu.utils.compat import force_cpu_devices

    force_cpu_devices(args.devices)

    from ddlpc_tpu.config import ExperimentConfig

    with open(args.config) as f:
        cfg = ExperimentConfig.from_dict(json.load(f))

    arms = {
        arm: run_arm(cfg, arm, args.micro_batch, args.sync_period)
        for arm in ["off"] + list(args.layout)
    }
    off = arms["off"]["state_bytes_per_device"]
    reductions = {}
    for name, arm in arms.items():
        if name == "off":
            continue
        b = arm["state_bytes_per_device"]
        reductions[name] = {
            kind: round(off[kind] / max(b[kind], 1), 2)
            for kind in ("params", "grads", "opt_state")
        }
        reductions[name]["state_total"] = round(
            arms["off"]["state_bytes_per_device_total"]
            / max(arm["state_bytes_per_device_total"], 1),
            2,
        )
    report = {
        "config": args.config,
        "devices": args.devices,
        "micro_batch_per_replica": args.micro_batch,
        "arms": arms,
        # Per-layout params/grads/opt_state reduction vs the replicated
        # baseline — the 1/N trajectory the acceptance gauge pins.
        "reduction_x": reductions,
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    atomic_write_json(args.out, report)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
