"""mIoU parity: this framework vs a PyTorch baseline on identical data.

The BASELINE north star is "Vaihingen mIoU within ±0.3 of a
PyTorch-equivalent baseline".  This script trains BOTH implementations of
the reference architecture — the reference's half-width U-Net
(DoubleConv/Down/Up with ConvTranspose, BatchNorm, ReLU; кластер.py:575-656)
— on byte-identical synthetic Vaihingen-like tiles with the same
optimizer/schedule, and reports held-out mIoU for each:

- torch: an independent, faithful PyTorch re-implementation of the
  reference model (NOT copied code; the reference file is 899 lines of
  which the model is ~80 — re-derived here from the SURVEY description),
  trained eagerly on CPU exactly like the reference's loop.
- jax: this framework's `unet` with reference-parity settings (stem none,
  conv_transpose, BatchNorm), trained through the compiled SPMD Trainer
  path on whatever backend is available.

Usage: python scripts/torch_parity.py [--epochs 15] [--size 128]
Writes a summary JSON to --out (default docs/parity/summary.json).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from ddlpc_tpu.utils.fsio import atomic_write_json  # noqa: E402

import numpy as np


def make_data(
    size: int,
    num_tiles: int = 127,
    test_split: int = 30,
    seed: int = 1,
    dataset: str = "synthetic",
):
    from ddlpc_tpu.data import train_test_split
    from ddlpc_tpu.data.datasets import SYNTHETIC_GENERATORS

    ds = SYNTHETIC_GENERATORS[dataset](num_tiles, (size, size), num_classes=6, seed=seed)
    return train_test_split(ds, test_split)


def miou_from_preds(preds: np.ndarray, labels: np.ndarray, C: int = 6) -> float:
    from ddlpc_tpu.ops.metrics import confusion_matrix, mean_iou

    return float(mean_iou(np.asarray(confusion_matrix(preds, labels, C))))


# --------------------------------------------------------------------------
# PyTorch side
# --------------------------------------------------------------------------


def run_torch(train_ds, test_ds, epochs: int, batch: int, lr: float, seed: int):
    import torch
    import torch.nn as nn

    torch.manual_seed(seed)

    def double_conv(cin, cout):
        return nn.Sequential(
            nn.Conv2d(cin, cout, 3, padding=1),
            nn.BatchNorm2d(cout),
            nn.ReLU(inplace=True),
            nn.Conv2d(cout, cout, 3, padding=1),
            nn.BatchNorm2d(cout),
            nn.ReLU(inplace=True),
        )

    class UNet(nn.Module):
        # Reference geometry at width_divisor=2: features 32,64,128,256,256
        # with a 256 bottleneck (кластер.py:620-656 with NN_in_model=2).
        def __init__(self, classes=6, feats=(32, 64, 128, 256, 256)):
            super().__init__()
            self.downs = nn.ModuleList()
            cin = 3
            for f in feats:
                self.downs.append(double_conv(cin, f))
                cin = f
            self.pool = nn.MaxPool2d(2)
            self.bottleneck = double_conv(cin, feats[-1])
            self.ups = nn.ModuleList()
            self.upconvs = nn.ModuleList()
            cin = feats[-1]
            for f in reversed(feats):
                self.upconvs.append(nn.ConvTranspose2d(cin, f, 2, stride=2))
                self.ups.append(double_conv(2 * f, f))
                cin = f
            self.head = nn.Conv2d(cin, classes, 1)

        def forward(self, x):
            skips = []
            for d in self.downs:
                x = d(x)
                skips.append(x)
                x = self.pool(x)
            x = self.bottleneck(x)
            for up, upc, skip in zip(self.ups, self.upconvs, reversed(skips)):
                x = upc(x)
                x = up(torch.cat([skip, x], dim=1))
            return self.head(x)

    model = UNet()
    opt = torch.optim.Adam(model.parameters(), lr=lr)
    loss_fn = nn.CrossEntropyLoss()
    x = torch.from_numpy(train_ds.images).permute(0, 3, 1, 2).contiguous()
    y = torch.from_numpy(train_ds.labels).long()
    n = len(train_ds)
    rng = np.random.default_rng(seed)
    model.train()
    for epoch in range(epochs):
        perm = rng.permutation(n)
        for s in range(0, n - batch + 1, batch):
            idx = torch.from_numpy(perm[s : s + batch])
            opt.zero_grad()
            out = model(x[idx])
            loss = loss_fn(out, y[idx])
            loss.backward()
            opt.step()
    model.eval()
    preds = []
    with torch.no_grad():
        tx = torch.from_numpy(test_ds.images).permute(0, 3, 1, 2).contiguous()
        for s in range(0, len(test_ds), batch):
            preds.append(model(tx[s : s + batch]).argmax(1).numpy())
    return miou_from_preds(np.concatenate(preds), test_ds.labels)


# --------------------------------------------------------------------------
# JAX side (this framework)
# --------------------------------------------------------------------------


def run_jax(
    size: int,
    epochs: int,
    batch: int,
    lr: float,
    seed: int,
    workdir: str,
    dataset: str = "synthetic",
):
    from ddlpc_tpu.config import (
        DataConfig,
        ExperimentConfig,
        ModelConfig,
        ParallelConfig,
        TrainConfig,
    )
    from ddlpc_tpu.train.trainer import Trainer

    cfg = ExperimentConfig(
        model=ModelConfig(width_divisor=2, num_classes=6),  # reference parity
        data=DataConfig(
            dataset=dataset,
            image_size=(size, size),
            synthetic_len=127,
            test_split=30,
            seed=1,
        ),
        train=TrainConfig(
            epochs=epochs,
            micro_batch_size=batch,
            sync_period=1,
            learning_rate=lr,
            seed=seed,
            dump_images_per_epoch=0,
            checkpoint_every_epochs=0,
            eval_every_epochs=epochs,
        ),
        parallel=ParallelConfig(data_axis_size=1),
        workdir=workdir,
    )
    rec = Trainer(cfg, resume=False).fit()
    return rec["val_miou"]


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=15)
    p.add_argument("--size", type=int, default=128)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--seeds", default="0,1,2")
    p.add_argument("--out", default="docs/parity/summary.json")
    p.add_argument(
        "--dataset",
        default="synthetic",
        choices=["synthetic", "synthetic_hard"],
        help="synthetic_hard = non-saturating task (converged mIoU < 1.0, "
        "so parity is measured where the metric discriminates)",
    )
    p.add_argument(
        "--arms",
        default="torch,jax",
        help="which sides to run this invocation; results merge into --out "
        "by (seed, side), so the ~hours torch CPU arm and the accelerator "
        "jax arm can run at different times without contending for the one "
        "host core / the one chip (512² round-4 protocol)",
    )
    p.add_argument(
        "--jax-platform", default="default",
        help="'cpu' forces the CPU backend for this invocation (torch-only "
        "arms force it automatically) — needed when no accelerator "
        "is attached, and gives a same-hardware CPU-vs-CPU comparison",
    )
    args = p.parse_args()

    arms = args.arms.split(",")
    if "jax" not in arms or args.jax_platform == "cpu":
        # The torch-only arm still computes mIoU through this framework's
        # jnp metrics; force the CPU backend BEFORE any jax use so a
        # dead/absent accelerator cannot block the final reduction
        # (a 2 h torch run once hung exactly there).
        import jax

        jax.config.update("jax_platforms", "cpu")
    train_ds, test_ds = make_data(args.size, dataset=args.dataset)
    config = {
        "arch": "reference-parity half-width U-Net (conv_transpose, BN)",
        "data": f"{args.dataset} {args.size}^2, 97 train / 30 test",
        "epochs": args.epochs,
        "batch": args.batch,
        "lr": args.lr,
    }
    # Merge with any existing partial summary (torch-only / jax-only runs)
    # — but ONLY if it was produced under the same protocol: pairing a
    # torch mIoU from one (dataset, size, epochs) with a jax mIoU from
    # another would report a meaningless delta.
    rows_by_seed: dict[int, dict] = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            prev = json.load(f)
        if prev.get("config") == config:
            for r in prev.get("runs", []):
                rows_by_seed[int(r["seed"])] = r
        else:
            print(
                f"existing {args.out} was a different protocol "
                f"({prev.get('config')}); starting fresh", file=sys.stderr
            )
    for seed in [int(s) for s in args.seeds.split(",")]:
        row = rows_by_seed.setdefault(seed, {"seed": seed})
        if "torch" in arms:
            t = run_torch(train_ds, test_ds, args.epochs, args.batch, args.lr, seed)
            row["torch_miou"] = round(t, 4)
        if "jax" in arms:
            j = run_jax(
                args.size, args.epochs, args.batch, args.lr, seed,
                workdir=f"/tmp/parity_jax_{args.dataset}_{args.size}_{seed}",
                dataset=args.dataset,
            )
            row["jax_miou"] = round(j, 4)
        print(json.dumps(row))
    rows = [rows_by_seed[k] for k in sorted(rows_by_seed)]
    done = [r for r in rows if "torch_miou" in r and "jax_miou" in r]
    summary = {"config": config, "runs": rows}
    if done:
        tm = float(np.mean([r["torch_miou"] for r in done]))
        jm = float(np.mean([r["jax_miou"] for r in done]))
        summary.update(
            torch_mean_miou=round(tm, 4),
            jax_mean_miou=round(jm, 4),
            delta=round(jm - tm, 4),
        )
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    atomic_write_json(args.out, summary)
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
