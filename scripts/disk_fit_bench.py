"""One REAL `fit()` from disk-loaded imagery on the accelerator.

VERDICT r3 missing #2: every committed training run fed from in-memory
synthetic arrays; the converters were unit-tested on fixtures but no
`fit()` had ever consumed their output through `load_tile_dir` /
`CropDataset`, and the ShardedLoader's host-upload path (the one a pod
uses) had no recorded accelerator run.  This script closes both:

1. Synthesizes ISPRS-geometry fixtures (orthophoto scenes + color-coded
   GT at the benchmark's conventions) and runs the REAL converter
   (`scripts/prepare_isprs.py`) on them → a scene directory of
   `<stem>.png` + `<stem>.npy` pairs.
2. Tiles one scene into a fixed 512² tile directory (`load_tile_dir`
   format) the way the reference's private pre-converted folder was laid
   out (кластер.py:660-674).
3. Runs the flagship architecture's `Trainer.fit()` TWICE from that disk
   data on the default backend (the real TPU under the driver):
   a. crop mode — `CropDataset` + `DihedralAugment` over the converter's
      scene dir, `ShardedLoader` host-upload path (`device_cache=False`);
   b. fixed-tile mode — `load_tile_dir` over the tiled directory, same
      upload path.
   Both record metrics + stage-resolved throughput into
   <outdir>/run.json.

The tiles/s here measures the HOST LINK (per-batch upload), not the chip:
docs/PERF.md carries the interpretation next to the device-cache numbers.

Usage: python scripts/disk_fit_bench.py [--epochs 2] [--out docs/disk_fit]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

_SCRIPTS_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_SCRIPTS_DIR))

import numpy as np


def write_fixtures(root: str, size: int = 1536, n_scenes: int = 3) -> tuple:
    """ISPRS-convention fixtures: top_mosaic_*.png + color-coded GT."""
    import imageio.v2 as imageio

    sys.path.insert(0, _SCRIPTS_DIR)
    from prepare_isprs import ISPRS_COLORS

    from ddlpc_tpu.data.datasets import SyntheticTiles

    tops = os.path.join(root, "top")
    gts = os.path.join(root, "gts")
    os.makedirs(tops), os.makedirs(gts)
    big = SyntheticTiles(
        num_tiles=n_scenes, image_size=(size, size), num_classes=6, seed=7
    )
    for i in range(n_scenes):
        img = (big.images[i] * 255).astype(np.uint8)
        lab = big.labels[i]
        imageio.imwrite(os.path.join(tops, f"top_mosaic_{i:02d}.png"), img)
        imageio.imwrite(
            os.path.join(gts, f"top_mosaic_{i:02d}_label.png"),
            ISPRS_COLORS[lab],
        )
    return tops, gts


def tile_scene_dir(scene_dir: str, out_dir: str, tile: int = 512) -> int:
    """Cut converter-output scenes into a fixed 512² tile dir
    (load_tile_dir format: <stem>.png + <stem>.npy), reference layout."""
    import imageio.v2 as imageio

    from ddlpc_tpu.data.datasets import load_scene_dir

    os.makedirs(out_dir, exist_ok=True)
    n = 0
    for si, (img, lab) in enumerate(load_scene_dir(scene_dir)):
        H, W = lab.shape
        for y in range(0, H - tile + 1, tile):
            for x in range(0, W - tile + 1, tile):
                stem = f"tile_{si}_{y}_{x}"
                imageio.imwrite(
                    os.path.join(out_dir, f"{stem}.png"),
                    (img[y : y + tile, x : x + tile] * 255).astype(np.uint8),
                )
                np.save(
                    os.path.join(out_dir, f"{stem}.npy"),
                    lab[y : y + tile, x : x + tile],
                )
                n += 1
    return n


def run_fit(tag: str, data_kw: dict, epochs: int, workdir: str) -> dict:
    from ddlpc_tpu.config import (
        CompressionConfig,
        DataConfig,
        ExperimentConfig,
        ModelConfig,
        ParallelConfig,
        TrainConfig,
    )
    from ddlpc_tpu.train.trainer import Trainer

    cfg = ExperimentConfig(
        # Flagship architecture (s2d×4 + DetailHead, bf16 head).  Batch
        # sized to the reference-scale dataset (micro 32 × sync 4 = one
        # 128-tile super-batch) rather than the device-cache benchmark's
        # B=128, so an epoch is data-defined, not wrap-dominated.
        model=ModelConfig(
            width_divisor=2, num_classes=6, stem="s2d", stem_factor=4,
            detail_head=True, head_dtype="bfloat16",
        ),
        data=DataConfig(num_classes=6, device_cache=False, **data_kw),
        train=TrainConfig(
            epochs=epochs,
            micro_batch_size=32,
            sync_period=4,
            learning_rate=1e-3,
            dump_images_per_epoch=0,
            checkpoint_every_epochs=0,
            eval_every_epochs=epochs,
            stall_timeout_s=900.0,
            stall_action="abort",
        ),
        parallel=ParallelConfig(data_axis_size=1),
        compression=CompressionConfig(mode="float16"),
        workdir=workdir,
    )
    t0 = time.perf_counter()
    trainer = Trainer(cfg, resume=False)
    rec = trainer.fit()
    rec = dict(rec)
    rec["tag"] = tag
    rec["wall_s"] = round(time.perf_counter() - t0, 1)
    rec["train_tiles"] = len(trainer.train_ds)
    return rec


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--outdir", default="docs/disk_fit")
    args = p.parse_args()

    tmp = tempfile.mkdtemp(prefix="diskfit_")
    tops, gts = write_fixtures(tmp)
    scenes = os.path.join(tmp, "scenes")
    # The REAL converter, as a user runs it.
    subprocess.run(
        [
            sys.executable,
            os.path.join(_SCRIPTS_DIR, "prepare_isprs.py"),
            "--images", tops, "--labels", gts, "--out", scenes,
        ],
        check=True,
    )
    tiles = os.path.join(tmp, "tiles")
    n_tiles = tile_scene_dir(scenes, tiles)
    print(f"fixtures ready: 3 scenes -> {n_tiles} fixed tiles", flush=True)

    results = [
        run_fit(
            "crop_augment_scene_dir",
            dict(
                data_dir=scenes,
                dataset="vaihingen",
                image_size=(512, 512),
                crops_per_epoch=128,
                test_split_scenes=1,
                test_split=8,
                augment=True,
            ),
            args.epochs,
            os.path.join(tmp, "run_crop"),
        ),
        run_fit(
            "fixed_tile_dir",
            dict(
                data_dir=tiles,
                dataset="vaihingen",
                image_size=(512, 512),
                test_split=4,
            ),
            args.epochs,
            os.path.join(tmp, "run_tiles"),
        ),
    ]
    for r in results:
        print(json.dumps(r), flush=True)
    os.makedirs(args.outdir, exist_ok=True)
    from ddlpc_tpu.utils.fsio import atomic_write_json

    atomic_write_json(
        os.path.join(args.outdir, "run.json"),
        {
            "note": (
                "Flagship-arch fit() from DISK through the REAL "
                "converter output and the ShardedLoader host-upload "
                "path (device_cache=False) on the default backend.  "
                "tiles_per_s measures the host-upload link, not the "
                "chip — see docs/PERF.md."
            ),
            "runs": results,
        },
    )
    print("disk fit bench OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
