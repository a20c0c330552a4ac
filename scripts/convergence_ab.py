"""Convergence A/B harness: stem factors and gradient-codec modes.

Trains the flagship U-Net on synthetic Vaihingen-like 512² tiles with the
WHOLE dataset device-resident (one upload, on-device batch gather), so the
comparison measures optimization quality, not host-link bandwidth —
re-uploading ~3 MB/tile (~400 MB/epoch) would otherwise weigh on 30-epoch
runs.

Two studies, both VERDICT r1 items:
- ``--stems 2,4``: does the faster stem_factor=4 pyramid (the headline
  bench config) match stem_factor=2 quality?
- ``--modes none,int8,float16``: the reference's research contribution is
  lossy gradient compression (кластер.py:255-557); this records what the
  codec costs in end-state mIoU vs the uncompressed control.

Writes one JSONL per variant under --outdir plus a summary table to stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ddlpc_tpu.config import (
    CompressionConfig,
    DataConfig,
    ExperimentConfig,
    ModelConfig,
    ParallelConfig,
    TrainConfig,
)
from ddlpc_tpu.data import train_test_split
from ddlpc_tpu.data.datasets import SYNTHETIC_GENERATORS
from ddlpc_tpu.models import build_model_from_experiment
from ddlpc_tpu.ops.metrics import accuracy_from_confusion, iou_per_class, mean_iou
from ddlpc_tpu.parallel.mesh import make_mesh
from ddlpc_tpu.parallel.train_step import (
    create_train_state,
    make_eval_step,
    make_train_step,
)
from ddlpc_tpu.train.optim import build_optimizer
from ddlpc_tpu.obs.schema import stamp  # noqa: E402
from ddlpc_tpu.utils.fsio import atomic_write_json, atomic_write_text  # noqa: E402


def run_variant(
    tag: str,
    stem_factor: int,
    mode: str,
    epochs: int,
    outdir: str,
    image_size=(512, 512),
    num_tiles=127,
    test_split=30,
    micro_batch=8,
    sync_period=4,
    seed=0,
    rounding: str = "nearest",
    dataset: str = "synthetic",
    head_dtype: str = "float32",
    learning_rate: float = 1e-3,
    detail_head: bool = False,
    detail_head_kind: str = "fullres",
    detail_head_hidden: int = 16,
    train_head_layout: str = "fullres",
    model_name: str = "unet",
    deep_supervision: bool = False,
    detail_head_scope: str = "per_head",
    compact_batch: bool = False,
    width_divisor: int = 2,
) -> dict:
    cfg = ExperimentConfig(
        model=ModelConfig(
            name=model_name,
            width_divisor=width_divisor,
            num_classes=6,
            stem="s2d" if stem_factor > 1 else "none",
            stem_factor=max(stem_factor, 2),
            head_dtype=head_dtype,
            detail_head=detail_head,
            detail_head_kind=detail_head_kind,
            detail_head_hidden=detail_head_hidden,
            train_head_layout=train_head_layout,
            deep_supervision=deep_supervision,
            detail_head_scope=detail_head_scope,
        ),
        data=DataConfig(image_size=image_size),
        train=TrainConfig(
            micro_batch_size=micro_batch,
            sync_period=sync_period,
            learning_rate=learning_rate,
            seed=seed,
        ),
        parallel=ParallelConfig(),
        compression=CompressionConfig(mode=mode, rounding=rounding),
    )
    mesh = make_mesh(cfg.parallel)
    n_dev = mesh.shape["data"]
    model = build_model_from_experiment(cfg)
    tx = build_optimizer(cfg.train)
    h, w = image_size
    state = create_train_state(model, tx, jax.random.key(seed), (1, h, w, 3))
    state = jax.device_put(state, NamedSharding(mesh, P()))
    # seed= so rounding='stochastic' arms draw seed-dependent codec noise
    # (the point of a seed sweep); the key stays resume-deterministic.
    step = make_train_step(model, tx, mesh, cfg.compression, seed=seed)
    eval_step = make_eval_step(model, mesh, cfg.model.num_classes)

    train_ds, test_ds = train_test_split(
        SYNTHETIC_GENERATORS[dataset](num_tiles, image_size, seed=1), test_split
    )
    repl = NamedSharding(mesh, P())
    # One upload; every batch is an on-device gather.  compact_batch (pod-
    # scale emulation, scripts/pod_lr_sweep.py): store/gather images as
    # bfloat16 and labels as int8 — numerically IDENTICAL training (the
    # model's first op casts inputs to its bf16 compute dtype anyway, and
    # labels only feed integer compare/one-hot ops), at 40% of the HBM a
    # super-batch of thousands of fp32 512² tiles would need.
    img_dt = jnp.bfloat16 if compact_batch else jnp.float32
    lab_dt = jnp.int8 if compact_batch else jnp.int32
    if compact_batch and cfg.model.num_classes > 127:
        raise ValueError("compact_batch int8 labels need num_classes <= 127")
    tr_x = jax.device_put(train_ds.images.astype(img_dt, copy=False), repl)
    tr_y = jax.device_put(train_ds.labels.astype(lab_dt, copy=False), repl)
    B = micro_batch * n_dev
    A = sync_period
    super_batch = B * A
    n = len(train_ds)
    batch_sh = NamedSharding(mesh, P(None, "data"))
    ev_sh = NamedSharding(mesh, P("data"))

    @jax.jit
    def gather_batch(x, y, idx):
        bx = jnp.take(x, idx, axis=0).reshape(A, B, h, w, 3)
        by = jnp.take(y, idx, axis=0).reshape(A, B, h, w)
        return (
            jax.lax.with_sharding_constraint(bx, batch_sh),
            jax.lax.with_sharding_constraint(by, batch_sh),
        )

    # Eval tiles resident too; batch = one multiple of the mesh.
    ev_b = max(n_dev, min(len(test_ds), 8 * n_dev) // n_dev * n_dev)
    pad = (-len(test_ds)) % ev_b
    ev_x = np.concatenate([test_ds.images, test_ds.images[:pad]]) if pad else test_ds.images
    ev_y = np.concatenate(
        [test_ds.labels, np.full((pad, h, w), -1, np.int32)]
    ) if pad else test_ds.labels
    ev_x_d = jax.device_put(ev_x, repl)
    ev_y_d = jax.device_put(ev_y, repl)

    @jax.jit
    def ev_slice(x, y, start):
        bx = jax.lax.dynamic_slice_in_dim(x, start, ev_b)
        by = jax.lax.dynamic_slice_in_dim(y, start, ev_b)
        return (
            jax.lax.with_sharding_constraint(bx, ev_sh),
            jax.lax.with_sharding_constraint(by, ev_sh),
        )

    def evaluate():
        cm = np.zeros((cfg.model.num_classes,) * 2, np.float64)
        for start in range(0, len(ev_x), ev_b):
            bx, by = ev_slice(ev_x_d, ev_y_d, start)
            out = eval_step(state, bx, by)
            cm += np.asarray(out["confusion"], np.float64)
        return {
            "val_miou": float(mean_iou(cm)),
            "val_pixel_acc": float(accuracy_from_confusion(cm)),
            # Per-class IoU: on the hard task the arms differ on the rare
            # sub-16-px classes (lines/discs/checker), not the bulk.
            "val_iou_per_class": [
                round(float(v), 4) for v in np.asarray(iou_per_class(cm))
            ],
        }

    os.makedirs(outdir, exist_ok=True)
    log_path = os.path.join(outdir, f"{tag}.jsonl")
    rng = np.random.default_rng(seed)
    rec = {}
    # Fresh stream per variant run, appended per epoch like every other
    # JSONL emitter (a torn rerun must not leave half-truncated rows).
    if os.path.exists(log_path):
        os.unlink(log_path)
    with open(log_path, "a") as log:
        for epoch in range(epochs):
            perm = rng.permutation(n)
            perm = np.resize(perm, -(-n // super_batch) * super_batch)
            losses = []
            for s in range(0, len(perm), super_batch):
                idx = jnp.asarray(perm[s : s + super_batch])
                bx, by = gather_batch(tr_x, tr_y, idx)
                state, m = step(state, bx, by)
                losses.append(m["loss"])
                # Free the device super-batch as soon as the step consumed
                # it: holding the python refs across iterations keeps TWO
                # super-batches alive, which at pod-emulation sizes (4096 ×
                # 512² bf16 ≈ 6.4 GB each) RESOURCE_EXHAUSTs the chip.
                del bx, by
            rec = {
                "tag": tag,
                "epoch": epoch,
                "loss": float(np.mean([float(l) for l in losses])),
            }
            if (epoch + 1) % 5 == 0 or epoch == epochs - 1:
                rec.update(evaluate())
            # stamp() mutates in place — stamp a copy so the returned rec
            # (merged into the committed summary.json) stays free of the
            # wall-clock "time" field, which would churn artifact diffs.
            log.write(json.dumps(stamp(dict(rec))) + "\n")
            log.flush()
    return rec


def merge_summary(
    outdir: str, results: "list[dict]", filename: str = "summary.json"
) -> None:
    """Merge rows into {outdir}/{filename} by tag: partial reruns of one
    study must never delete another study's committed headline entries.
    Shared by the convergence-style sweep drivers in scripts/ (the bench
    drivers keep their own incremental per-row writes)."""
    summary_path = os.path.join(outdir, filename)
    merged = {}
    if os.path.exists(summary_path):
        with open(summary_path) as f:
            merged = {r["tag"]: r for r in json.load(f)}
    merged.update({r["tag"]: r for r in results})
    atomic_write_json(summary_path, list(merged.values()))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--stems", default="", help="comma list, e.g. 2,4")
    p.add_argument("--modes", default="", help="comma list, e.g. none,int8,float16")
    p.add_argument("--stem-for-modes", type=int, default=4)
    p.add_argument("--epochs", type=int, default=40)
    p.add_argument("--outdir", default="runs/convergence_ab")
    p.add_argument(
        "--roundings",
        default="",
        help="comma list, e.g. nearest,stochastic — A/Bs the int8 codec's "
        "rounding rule at full 512² scale (docs/QUANTIZATION.md)",
    )
    p.add_argument(
        "--heads",
        default="",
        help="comma list of head dtypes, e.g. float32,bfloat16 — A/Bs the "
        "bf16 logit-storage optimization's quality cost (docs/PERF.md)",
    )
    p.add_argument(
        "--dataset",
        default="synthetic",
        choices=["synthetic", "synthetic_hard"],
        help="synthetic_hard = the non-saturating task (sub-16-px structure, "
        "class imbalance) whose converged mIoU stays < 1.0 so arms separate",
    )
    p.add_argument("--stems-none", action="store_true",
                   help="include a stem-free (reference-layout) arm in --stems")
    p.add_argument(
        "--details",
        default="",
        help="comma list of stem factors to run WITH the full-res DetailHead "
        "(models/layers.py) — the refinement that restores sub-stem-px "
        "structure; tags get a _detail suffix",
    )
    args = p.parse_args()
    ds = args.dataset
    # Tag suffix keeps hard-task rows distinct from the legacy saturating
    # rows inside the same summary.json.
    sfx = "_hard" if ds == "synthetic_hard" else ""

    results = []
    stems = [int(s) for s in args.stems.split(",") if s]
    if args.stems_none:
        stems = [1] + stems
    for sf in stems:
        results.append(
            run_variant(
                f"stem{sf}_fp16{sfx}", sf, "float16", args.epochs,
                args.outdir, dataset=ds,
            )
        )
        print(json.dumps(results[-1]))
    for sf in [int(s) for s in args.details.split(",") if s]:
        results.append(
            run_variant(
                f"stem{sf}_detail_fp16{sfx}", sf, "float16", args.epochs,
                args.outdir, dataset=ds, detail_head=True,
            )
        )
        print(json.dumps(results[-1]))
    for mode in [m for m in args.modes.split(",") if m]:
        results.append(
            run_variant(
                f"mode_{mode}_stem{args.stem_for_modes}{sfx}",
                args.stem_for_modes,
                mode,
                args.epochs,
                args.outdir,
                dataset=ds,
            )
        )
        print(json.dumps(results[-1]))
    for head in [h for h in args.heads.split(",") if h]:
        results.append(
            run_variant(
                f"head_{head}_stem{args.stem_for_modes}{sfx}",
                args.stem_for_modes,
                "none",
                args.epochs,
                args.outdir,
                dataset=ds,
                head_dtype=head,
            )
        )
        print(json.dumps(results[-1]))
    for rounding in [r for r in args.roundings.split(",") if r]:
        tag = f"int8_{rounding}_stem{args.stem_for_modes}{sfx}"
        src_tag = f"mode_int8_stem{args.stem_for_modes}{sfx}"
        src = next((r for r in results if r["tag"] == src_tag), None)
        if rounding == "nearest" and src is not None:
            # int8+nearest IS the --modes int8 variant (nearest is the
            # default rounding): alias instead of re-burning a 40-epoch
            # accelerator run on identical numbers.
            rec = dict(src, tag=tag)
            # Rewrite the per-epoch records' tag too, so consumers grouping
            # jsonl lines by tag (not filename) attribute them correctly.
            with open(os.path.join(args.outdir, f"{src_tag}.jsonl")) as fin:
                retagged = "".join(
                    json.dumps(dict(json.loads(line), tag=tag)) + "\n"
                    for line in fin
                )
            atomic_write_text(
                os.path.join(args.outdir, f"{tag}.jsonl"), retagged
            )
        else:
            rec = run_variant(
                tag,
                args.stem_for_modes,
                "int8",
                args.epochs,
                args.outdir,
                rounding=rounding,
                dataset=ds,
            )
        results.append(rec)
        print(json.dumps(results[-1]))
    merge_summary(args.outdir, results)


if __name__ == "__main__":
    main()
