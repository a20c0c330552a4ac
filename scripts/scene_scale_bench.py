"""Reference-scale scene pipeline: 33 Vaihingen-geometry orthophotos.

VERDICT r4 missing #4 / next #5: every disk-path run so far used small
fixtures (3 scenes at 1536²); the real Vaihingen benchmark is ~33 scenes
of multi-thousand-pixel orthophotos, and the reference's design — eager
whole-directory load (кластер.py:660-674) — has never been exercised at
that volume.  Synthetic pixels are fine (geometry and volume are the
test); this script:

1. Generates 33 scenes at Vaihingen-like sizes (~2500×2000 px, varied per
   scene the way the real mosaic tiles vary), STREAMED one scene at a
   time so fixture generation itself stays in bounded memory.
2. Runs the REAL converter (`scripts/prepare_isprs.py`) over the full set
   and records wall time, scenes/s, MPix/s, and the converter's peak RSS.
3. Eager-loads the converted directory via `load_scene_dir` — the
   reference's own design decision — and records load time and the peak
   RSS that decision costs at reference scale (the number that tells a
   user whether their host fits the eager design).
4. Builds `CropDataset` + `DihedralAugment` over all 33 scenes and
   measures host-side crop throughput (crops/s at 512²).
5. Runs a short flagship-architecture `fit()` from those crops on the CPU
   backend (forced — this bench measures the host, not the chip) and
   records tiles/s through the real Trainer loop.

Phases 3-5 run in a subprocess so their peak RSS is attributable (the
parent's fixture buffers don't inflate the measurement).

Output: one JSON file (default docs/disk_fit/scene_scale.json).

Usage: python scripts/scene_scale_bench.py [--scenes 33] [--steps 8]
       [--out docs/disk_fit/scene_scale.json] [--keep-fixtures DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import tempfile
import time

_SCRIPTS_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_SCRIPTS_DIR)
sys.path.insert(0, _REPO)
sys.path.insert(0, _SCRIPTS_DIR)

import numpy as np

from ddlpc_tpu.utils.fsio import atomic_write_json  # noqa: E402

# Vaihingen's 33 mosaic tiles vary around ~2500×2000; reproduce that
# spread so no single shape hides a stride bug.
SIZES = [(2566, 1893), (2428, 2006), (2500, 1934), (1281, 2336),
         (2546, 1903), (2064, 2494)]


def write_fixtures(root: str, n_scenes: int, seed: int = 11) -> dict:
    """Stream n_scenes ISPRS-convention fixtures to disk one at a time."""
    import imageio.v2 as imageio

    from prepare_isprs import ISPRS_COLORS
    from ddlpc_tpu.data.datasets import SyntheticTiles

    tops, gts = os.path.join(root, "top"), os.path.join(root, "gts")
    os.makedirs(tops), os.makedirs(gts)
    t0 = time.perf_counter()
    px = 0
    for i in range(n_scenes):
        h, w = SIZES[i % len(SIZES)]
        ds = SyntheticTiles(
            num_tiles=1, image_size=(h, w), num_classes=6, seed=seed + i
        )
        img = (ds.images[0] * 255).astype(np.uint8)
        lab = ds.labels[0]
        imageio.imwrite(os.path.join(tops, f"top_mosaic_{i:02d}.png"), img)
        imageio.imwrite(
            os.path.join(gts, f"top_mosaic_{i:02d}_label.png"),
            ISPRS_COLORS[lab],
        )
        px += h * w
        del ds, img, lab
    return {
        "n_scenes": n_scenes,
        "total_mpix": round(px / 1e6, 1),
        "fixture_gen_s": round(time.perf_counter() - t0, 2),
    }


def run_converter(tops: str, gts: str, out_dir: str, fmt: str = "png") -> dict:
    """The real prepare_isprs.py over the full scene set, as a subprocess
    (its peak RSS lands in RUSAGE_CHILDREN, separable from ours)."""
    before = resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.join(_SCRIPTS_DIR, "prepare_isprs.py"),
         "--images", tops, "--labels", gts, "--out", out_dir,
         "--format", fmt],
        capture_output=True, text=True, timeout=3600,
    )
    dt = time.perf_counter() - t0
    if proc.returncode != 0:
        raise RuntimeError(f"converter failed:\n{proc.stderr[-2000:]}")
    after = resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss
    return {
        "convert_s": round(dt, 2),
        "converter_peak_rss_mb": round(max(after, before) / 1024, 1),
        "converter_stdout_tail": proc.stdout.strip().splitlines()[-1:],
    }


_CHILD_CODE = r"""
import json, os, resource, sys, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")  # host-side bench: never take the chip
sys.path.insert(0, {repo!r})

from ddlpc_tpu.data.datasets import CropDataset, DihedralAugment, load_scene_dir

rec = {{}}
def rss_mb():
    return round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024, 1)

MMAP = {mmap}
PFX = "mmap_" if MMAP else "eager_"
# -- phase: whole-dir load.  Eager = the reference's design
# (кластер.py:660-674); mmap = the round-5 escape hatch for corpora whose
# eager bill doesn't fit (load_scene_dir(mmap=True), uint8 npy scenes).
t0 = time.perf_counter()
scenes = load_scene_dir({scene_dir!r}, mmap=MMAP)
rec[PFX + "load_s"] = round(time.perf_counter() - t0, 2)
rec[PFX + "scenes"] = len(scenes)
rec[PFX + "peak_rss_mb"] = rss_mb()
rec[PFX + "bytes_mb"] = round(sum(
    i.nbytes + l.nbytes for i, l in scenes) / 2**20, 1)

# -- phase: CropDataset host throughput at the reference crop size
ds = CropDataset(scenes, (512, 512), crops_per_epoch=256, seed=0)
aug = DihedralAugment(ds, seed=0)
t0 = time.perf_counter()
n = 0
for epoch in range(2):
    aug.set_epoch(epoch)
    for start in range(0, len(aug), 32):
        idx = np.arange(start, min(start + 32, len(aug)))
        imgs, labs = aug.gather(idx)
        n += len(idx)
rec[PFX + "crop_throughput_per_s"] = round(n / (time.perf_counter() - t0), 1)
rec[PFX + "crop_peak_rss_mb"] = rss_mb()
del aug, ds, scenes

if not {do_fit}:
    print("CHILD_JSON " + json.dumps(rec))
    raise SystemExit(0)

# -- phase: real Trainer.fit() from those crops, CPU backend
from ddlpc_tpu.config import (CompressionConfig, DataConfig, ExperimentConfig,
                              ModelConfig, ParallelConfig, TrainConfig)
from ddlpc_tpu.train.trainer import Trainer

steps = {steps}
cfg = ExperimentConfig(
    model=ModelConfig(width_divisor=2, num_classes=6, stem="s2d",
                      stem_factor=4, detail_head=True, head_dtype="bfloat16"),
    data=DataConfig(num_classes=6, device_cache=False, data_dir={scene_dir!r},
                    image_size=(512, 512), crops_per_epoch=steps * 8,
                    augment=True, test_split_scenes=1),
    train=TrainConfig(epochs=1, micro_batch_size=8, sync_period=1,
                      learning_rate=1e-3, dump_images_per_epoch=0,
                      checkpoint_every_epochs=0, eval_every_epochs=0,
                      stall_timeout_s=1800.0, stall_action="abort"),
    parallel=ParallelConfig(data_axis_size=1),
    compression=CompressionConfig(mode="float16"),
    workdir={workdir!r},
)
t0 = time.perf_counter()
trainer = Trainer(cfg, resume=False)
fit_rec = trainer.fit()
dt = time.perf_counter() - t0
rec["fit_backend"] = jax.default_backend()
rec["fit_tiles"] = steps * 8
rec["fit_s"] = round(dt, 2)
rec["fit_tiles_per_s"] = round(steps * 8 / dt, 2)
rec["fit_final_loss"] = float(fit_rec.get("loss", float("nan")))
rec["fit_peak_rss_mb"] = rss_mb()
print("CHILD_JSON " + json.dumps(rec))
"""


def run_load_and_fit(
    scene_dir: str, workdir: str, steps: int,
    mmap: bool = False, do_fit: bool = True,
) -> dict:
    code = _CHILD_CODE.format(
        repo=_REPO, scene_dir=scene_dir, workdir=workdir, steps=steps,
        mmap=mmap, do_fit=do_fit,
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + ":" + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=7200, env=env,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"load/fit child failed:\n{proc.stderr[-3000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("CHILD_JSON "):
            return json.loads(line[len("CHILD_JSON "):])
    raise RuntimeError(f"no CHILD_JSON in output:\n{proc.stdout[-2000:]}")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--scenes", type=int, default=33)
    p.add_argument("--steps", type=int, default=8,
                   help="fit() optimizer steps (micro 8 each)")
    p.add_argument("--out", default="docs/disk_fit/scene_scale.json")
    p.add_argument("--keep-fixtures", default="",
                   help="persist fixtures/converted scenes here (else tmp)")
    p.add_argument("--mode", default="full", choices=["full", "mmap-only"],
                   help="mmap-only: converter --format npy + the mmap load/"
                        "crop arm only, merged into an existing --out")
    args = p.parse_args()

    root_ctx = (
        tempfile.TemporaryDirectory(prefix="scene_scale_")
        if not args.keep_fixtures else None
    )
    root = root_ctx.name if root_ctx else args.keep_fixtures
    os.makedirs(root, exist_ok=True)
    mmap_only = args.mode == "mmap-only"
    try:
        rec = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                prior = json.load(f)
            # Only merge arms measured on the SAME corpus — mixing a
            # 33-scene eager arm with a 10-scene mmap arm under one header
            # would be an apples-to-oranges table with no provenance.
            if prior.get("n_scenes") == args.scenes:
                rec = prior
            else:
                print(f"note: {args.out} holds a {prior.get('n_scenes')}-"
                      f"scene run; starting fresh for --scenes "
                      f"{args.scenes}", flush=True)
        rec.update({"sizes_px": SIZES, "crop_size": 512})
        print(f"[1/4] fixtures → {root}", flush=True)
        rec.update(write_fixtures(root, args.scenes))
        print(f"      {rec['n_scenes']} scenes, {rec['total_mpix']} MPix "
              f"in {rec['fixture_gen_s']}s", flush=True)

        fmt = "npy" if mmap_only else "png"
        scene_dir = os.path.join(root, "scenes_" + fmt)
        print(f"[2/4] real converter (prepare_isprs.py, --format {fmt})",
              flush=True)
        conv = run_converter(
            os.path.join(root, "top"), os.path.join(root, "gts"), scene_dir,
            fmt=fmt,
        )
        pfx = "npy_" if mmap_only else ""
        rec.update({pfx + k: v for k, v in conv.items()})
        rec[pfx + "convert_mpix_per_s"] = round(
            rec["total_mpix"] / conv["convert_s"], 2
        )
        print(f"      {conv['convert_s']}s "
              f"({rec[pfx + 'convert_mpix_per_s']} MPix/s, "
              f"peak RSS {conv['converter_peak_rss_mb']} MB)", flush=True)

        label = "mmap load + crops" if mmap_only else "eager load + crops + fit()"
        print(f"[3/4+4/4] {label} (subprocess, CPU)", flush=True)
        with tempfile.TemporaryDirectory(prefix="scene_fit_") as wd:
            rec.update(run_load_and_fit(
                scene_dir, wd, args.steps,
                mmap=mmap_only, do_fit=not mmap_only,
            ))
        arm = "mmap" if mmap_only else "eager"
        msg = (f"      {arm} {rec[arm + '_load_s']}s / "
               f"{rec[arm + '_peak_rss_mb']} MB RSS "
               f"({rec[arm + '_bytes_mb']} MB arrays); "
               f"crops {rec[arm + '_crop_throughput_per_s']}/s")
        if not mmap_only:
            msg += (f"; fit {rec['fit_tiles_per_s']} tiles/s "
                    f"on {rec['fit_backend']}")
        print(msg, flush=True)

        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        atomic_write_json(args.out, rec)
        print(f"wrote {args.out}", flush=True)
    finally:
        if root_ctx:
            root_ctx.cleanup()


if __name__ == "__main__":
    main()
